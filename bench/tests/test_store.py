"""The corpus/index cache in bench/.cache: a hit loads what was built, and any change
to the configuration or to the sources that build it misses."""

import shutil

import numpy as np

from bench import store
from bench.spec import ROOT

CORPUS = dict(n_docs=1024, vocab=256, n_topics=4, doc_len_mean=24, query_len_mean=8,
              topic_concentration=0.25, seed=0)
INDEX = dict(b=8, c=8, bound_bits=4, doc_bits=8, quant_granularity="row", build_flat_inv=True,
             build_avg=True, lane_pad=8, d_proj=16, kmeans_iters=1, seed=0)


def test_keys_follow_configuration_and_sources(tmp_path):
    for p in store.INDEX_SOURCES + store.CORPUS_SOURCES:
        src = ROOT / p
        dst = tmp_path / p
        dst.parent.mkdir(parents=True, exist_ok=True)
        (shutil.copytree if src.is_dir() else shutil.copy)(src, dst)
    ck, ik = store.corpus_key(CORPUS, tmp_path), store.index_key(CORPUS, INDEX, "cpu", tmp_path)
    assert ck == store.corpus_key(CORPUS, ROOT) and ik == store.index_key(CORPUS, INDEX, "cpu", ROOT)
    assert store.corpus_key(dict(CORPUS, seed=1), tmp_path) != ck
    assert store.index_key(dict(CORPUS, seed=1), INDEX, "cpu", tmp_path) != ik
    assert store.index_key(CORPUS, dict(INDEX, doc_bits=4), "cpu", tmp_path) != ik
    assert store.index_key(CORPUS, INDEX, "tpu", tmp_path) != ik
    builder = tmp_path / "src/repro/index/builder.py"
    builder.write_text(builder.read_text() + "\n# changed\n")
    assert store.index_key(CORPUS, INDEX, "cpu", tmp_path) != ik
    assert store.corpus_key(CORPUS, tmp_path) == ck
    gen = tmp_path / "bench/corpus.py"
    gen.write_text(gen.read_text() + "\n# changed\n")
    assert store.corpus_key(CORPUS, tmp_path) != ck


def test_hit_loads_what_was_built_and_a_changed_key_misses(tmp_path):
    logs = []
    corpus = store.load_corpus(CORPUS, tmp_path, logs.append)
    again = store.load_corpus(CORPUS, tmp_path, logs.append)
    assert len(logs) == 1 and all(np.array_equal(a, b) for a, b in zip(corpus, again))
    a = store.load_index(CORPUS, INDEX, corpus, tmp_path, logs.append)
    b = store.load_index(CORPUS, INDEX, corpus, tmp_path, logs.append)
    assert len(logs) == 2  # built once
    assert np.array_equal(np.asarray(a.docs_fwdq.ws), np.asarray(b.docs_fwdq.ws))
    c = store.load_index(CORPUS, dict(INDEX, doc_bits=4), corpus, tmp_path, logs.append)
    assert len(logs) == 3 and int(np.asarray(c.docs_fwdq.ws).max()) <= 15
    assert len(list((tmp_path / "index").iterdir())) == 2
