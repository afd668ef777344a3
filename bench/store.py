"""The corpus and index a cell serves, kept in ``bench/.cache/`` of the checkout.

A deployed server restarts from its index on disk; it does not rebuild it. So the
first run of a configuration in a checkout generates the corpus and builds the index
(``repro.index.builder.build_index``) and writes both here, the index through the
program's own store (``repro.index.store.save_index``); every later run loads them.
Each entry is named by a key over everything that decides its bytes: the
configuration's corpus and index sections, the sources that generate and build them
(``bench/corpus.py`` and every file under ``src/repro/index/``), the JAX version and
the platform that built it. A change to any of these gives a new key, so a stale
corpus or index is never served.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from pathlib import Path

import numpy as np

from bench.corpus import Corpus, make_corpus
from bench.spec import BENCH, ROOT

CACHE = BENCH / ".cache"
INDEX_SOURCES = ("src/repro/index",)
CORPUS_SOURCES = ("bench/corpus.py",)


def source_hash(paths, root: Path = ROOT) -> str:
    """blake2b over the relative path and bytes of every ``.py`` file under ``paths``."""
    h = hashlib.blake2b(digest_size=16)
    for p in paths:
        base = root / p
        files = sorted(base.rglob("*.py")) if base.is_dir() else [base]
        for f in files:
            h.update(str(f.relative_to(root)).encode() + b"\0" + f.read_bytes())
    return h.hexdigest()


def _key(parts: dict) -> str:
    return hashlib.blake2b(json.dumps(parts, sort_keys=True).encode(), digest_size=12).hexdigest()


def corpus_key(corpus_cfg: dict, root: Path = ROOT) -> str:
    return _key({"corpus": corpus_cfg, "src": source_hash(CORPUS_SOURCES, root)})


def index_key(corpus_cfg: dict, index_cfg: dict, platform: str, root: Path = ROOT) -> str:
    import jax

    return _key({
        "corpus": corpus_key(corpus_cfg, root), "index": index_cfg, "platform": platform,
        "src": source_hash(INDEX_SOURCES, root), "jax": jax.__version__,
    })


def load_corpus(corpus_cfg: dict, cache: Path = CACHE, log=print) -> Corpus:
    path = cache / "corpus" / f"{corpus_key(corpus_cfg)}.npz"
    if path.exists():
        with np.load(path) as z:
            return Corpus(z["doc_ptr"], z["tids"], z["ws"], int(z["vocab"]), z["doc_topic"])
    t0 = time.perf_counter()
    corpus = make_corpus(corpus_cfg)
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(path.stem + ".tmp.npz")
    np.savez(tmp, doc_ptr=corpus.doc_ptr, tids=corpus.tids, ws=corpus.ws,
             vocab=corpus.vocab, doc_topic=corpus.doc_topic)
    os.replace(tmp, path)
    log(f"corpus generated in {time.perf_counter() - t0:.1f} s and written to {path}")
    return corpus


def load_index(corpus_cfg: dict, index_cfg: dict, corpus: Corpus, cache: Path = CACHE, log=print):
    """The index of this corpus under ``index_cfg`` (``IndexBuildConfig`` fields), on
    the default device: loaded from the cache, or built and written there first."""
    import jax

    from repro.index.builder import IndexBuildConfig, build_index
    from repro.index.store import load_index as load_stored
    from repro.index.store import save_index

    directory = cache / "index" / index_key(corpus_cfg, index_cfg, jax.default_backend())
    if not (directory / ".complete").exists():
        cfg = IndexBuildConfig(**index_cfg)
        t0 = time.perf_counter()
        index = build_index(corpus.doc_ptr, corpus.tids, corpus.ws, corpus.vocab, cfg)
        jax.block_until_ready(index)
        t1 = time.perf_counter()
        save_index(str(directory), index, cfg)
        log(f"index built in {t1 - t0:.1f} s, written in {time.perf_counter() - t1:.1f} s "
            f"to {directory}")
        del index
    return load_stored(str(directory), mmap=True, device=True)
