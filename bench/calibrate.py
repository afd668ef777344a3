"""Readings that the limits of ``correct`` are set from, on the chip at a cell's size.

    python3 bench/calibrate.py --workload <cell> --seeds 3 --control-seeds 3 \
        --fault-seeds 3 --seconds 30 --first-seed <n>

``--seconds`` is the program's and the control's window: long enough at the cell's
load for as many answers as a run compares (its traffic's ``sample``).

In one process: the cell run (``harness.execute``, the same path as ``run.py``) on
``--seeds`` seeds with the program as configured, then on ``--control-seeds`` seeds
with the configuration's ``control`` applied (the program's own lower-precision path:
4-bit document weights in place of 8-bit); then, on ``--fault-seeds`` seeds, the
reference put in the program's place with a top-k fault planted: ranks k+1..2k of the
exact order returned as the top k, over the sample that a run of the seed compares.
Each prints one JSON line with its compared numbers. The lower reading of a number is
the largest over the program's seeds (these and the benchmark's own runs), the upper
the smallest over the control's or the fault's; ``PERF.md`` gives both and the limit
set between them. The benchmark's own runs never run this.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / "bench" / ".cache" / "jax")
os.environ.setdefault("TPU_LOG_DIR", str(ROOT / "bench" / ".cache" / "tpu_logs"))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=3)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--fault-seeds", type=int, default=3)
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--first-seed", type=int, required=True)
    args = p.parse_args(argv)

    import jax

    from bench import harness, spec

    if jax.devices()[0].platform != "tpu":
        print("[calibrate] needs a TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    bm = spec.load_benchmark()
    wl = spec.workload(bm, args.workload)
    cfg = spec.load_json(spec.config_path(wl["config"]))
    control = copy.deepcopy(cfg)
    control["index"].update(cfg["control"]["index"])
    runs = [("program", cfg, args.first_seed + i) for i in range(args.seeds)]
    runs += [("control", control, args.first_seed + 1000 + i) for i in range(args.control_seeds)]
    for arm, c, seed in runs:
        out = harness.execute(bm, args.workload, seed, args.seconds, False, time.monotonic(),
                              config=c)
        line = {"arm": arm, "seed": seed, "correct": out["correct"],
                "recall_at_k": out["metrics"].get("recall_at_k", {}).get("value"),
                **{k: v["value"] for k, v in out["checks"].items()}}
        print(json.dumps(line), flush=True)
    tr = spec.load_json(spec.traffic_path(wl["traffic"]))
    for i in range(args.fault_seeds):
        seed = args.first_seed + 2000 + i
        line = {"arm": "topk_shift_in_reference", "seed": seed,
                "recall_short": topk_shift_reading(cfg, tr, seed, bm["run_seconds"])}
        print(json.dumps(line), flush=True)
    return 0


def topk_shift_reading(cfg: dict, tr: dict, seed: int, seconds: float) -> float:
    """recall_short of the exact ranks k+1..2k taken as the top k, over the sample
    that a run of this seed compares (every request of its plan taken as finished)."""
    import numpy as np

    from bench import check, reference, store
    from bench.traffic import make_plan, sample_positions

    k = cfg["query"]["k"]
    corpus = store.load_corpus(cfg["corpus"])
    plan = make_plan(tr, cfg["corpus"], corpus, seed, seconds)
    finished = np.ones(len(plan.stream), bool)
    longest = int(np.argmax([len(t) for t, _ in plan.stream]))
    pos = sample_positions(seed, finished, tr["sample"], longest)
    ids, _ = reference.exact_topk(corpus, [plan.stream[i] for i in pos], 2 * k)
    return 1.0 - check.recall(ids[:, k:], ids[:, :k])


if __name__ == "__main__":
    sys.exit(main())
