"""Run one cell of the on-chip serving benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

From the root of a checkout, as a fresh process on a machine that holds the
cell's chips.  It sets up (weights from the seed, the plan, the engine),
warms up every shape the cell uses, runs the closed loop for ``--seconds``,
checks the served tokens against the plain float32 reference, and prints
one JSON object as the last line of standard output.  ``--trace 1`` reports
the per-layer metrics of a profiled window instead of the end-to-end ones.

It fails, and prints no result, when JAX finds no TPU or fewer chips than
the cell asks for.  ``--rehearse`` runs the same steps at a tiny size on
whatever JAX finds (the CPU here) and reports no metric.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny size on any platform; reports no metric")
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profile here (default: a temporary "
                         "directory, removed after reading)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    here = str(Path(__file__).resolve().parent)
    sys.path[:] = [p for p in sys.path if p and Path(p).resolve() != Path(here)]
    for p in (ROOT / "src", ROOT):
        sys.path.insert(0, str(p))
    from benchmarks.chip import harness
    try:
        result = harness.run(args, T_START)
    except harness.NoChip as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    harness.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
