"""Readings that a cell's correctness limit is set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seconds <s> \\
        --seeds <n> [<n> ...] [--out <file.json>]

For each seed, in one process: a whole run of the cell at its own size and
load (set-up, warm-up, the closed loop for ``--seconds``), then the
widest logit gap of the tokens the program served and the decision on
them, and the run's decision with the fp8 control in the program's place:
the tokens it puts first at the same positions, scored by the same
comparison, which has to come out not correct.  The benchmark's own runs
never compute the control.  Prints one JSON line per seed.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    here = Path(__file__).resolve().parent
    sys.path[:] = [p for p in sys.path if p and Path(p).resolve() != here]
    for p in (ROOT / "src", ROOT):
        sys.path.insert(0, str(p))
    from benchmarks.chip import harness
    from benchmarks.chip.compiles import Compiles
    compiles = Compiles()
    rows = []
    for seed in args.seeds:
        run_args = argparse.Namespace(
            workload=args.workload, seed=seed, seconds=args.seconds,
            trace=0, rehearse=args.rehearse, trace_dir=None)
        r = harness.run(run_args, time.perf_counter(), compiles=compiles,
                        control=True)
        c = r["compared"]
        row = {"seed": seed, "correct": r["program"]["correct"],
               "max_logit_gap": r["program"]["max_logit_gap"],
               "control_correct": r["correct"],
               "control_max_logit_gap": c["max_logit_gap"]["value"],
               "limit": c["max_logit_gap"]["limit"],
               "slot_mismatches": c["slot_mismatches"]["value"],
               "requests_compared": c["requests_compared"]["value"],
               "served_tokens_compared": r["served_tokens_compared"],
               "memory_peak_bytes": r["device"]["memory_peak_bytes"]}
        print(json.dumps(row), flush=True)
        rows.append(row)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
