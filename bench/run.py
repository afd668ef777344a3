"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and a traffic mix,
each a file under ``bench/``. The last line of standard output is one JSON object
with ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared, with its limit. The same
numbers are the last lines of standard error.

It needs the chip: where JAX finds no TPU, or fewer chips than the cell asks for, it
exits with 2 and prints no result. JAX's persistent compilation cache is kept in
``bench/.cache/jax`` of the checkout, so only a checkout's first run compiles, and the
TPU runtime's logs go to ``bench/.cache/tpu_logs`` unless ``TPU_LOG_DIR`` says
otherwise.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
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
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    from bench import harness, spec

    bm = spec.load_benchmark()
    wl = spec.workload(bm, args.workload)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < wl["chips"]:
        print(f"[bench] cell {args.workload} needs {wl['chips']} TPU chip(s); JAX sees "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 2
    # every program goes to the cache, so a checkout's later runs compile nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    harness.log(f"device: {devices[0].device_kind} x{len(devices)}; "
                f"compile cache {enable_compile_cache()}")
    result = harness.execute(bm, args.workload, args.seed, args.seconds, bool(args.trace),
                             T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
