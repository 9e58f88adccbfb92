"""Readings that the correctness limits are set from.

    python3 -m bench.control --workload <name> --seconds <s> --seeds 1 2 3

Runs the cell once per seed in one process, as ``bench.run`` does, with
the float8 control in the served program's place: at each served position
of the same prompts and tokens, the token the control puts first is scored
by the comparison that decides ``correct``. One JSON line per seed, with
the control's widest logit gap (which has to fail the limit, so
``correct`` is false), the served program's own gap on the same run (which
has to pass it) and the limit. The benchmark's own runs never run the
control.
"""
import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    from bench import harness
    cell = harness.load_cell(args.workload)
    for seed in args.seeds:
        out = harness.run_cell(cell, seed, args.seconds, False,
                               t_start=time.perf_counter(), control=True)
        c = out["compared"]
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "control_logit_gap": c["logit_gap"]["value"],
            "program_logit_gap": out["program_logit_gap"],
            "served_checked": c["served_checked"]["value"],
            "limit": c["logit_gap"]["limit"]}), flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
