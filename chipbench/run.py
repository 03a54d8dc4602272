"""Run one benchmark cell once, on the chip this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``: each
number the correctness check compared, beside its limit.  The same
numbers are the last lines of standard error.  Without a TPU, or with
fewer chips than the cell asks for, it exits non-zero and prints no
result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    # JAX's persistent compilation cache lives at a fixed path inside the
    # checkout; JAX reads the variable when it is imported.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    # every run plans cold: no plan cache on disk
    os.environ.pop("REPRO_PLANCACHE_DIR", None)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench.bench.harness import main as run

    return run(args, T_START)


if __name__ == "__main__":
    sys.exit(main())
