"""Run one cell of the benchmark on the chip this process finds.

    python3 -m bench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell (a ``workloads`` entry of
``BENCHMARK.json``) names a configuration (``bench/configs/<name>.json``,
which names its architecture module ``bench/archs/<name>.py``) and a
traffic mix (``bench/traffic/<name>.json``); ``--trace 1`` reports
the cell's per-layer metrics, each read by ``bench/metrics/<name>.py``,
and ``--trace 0`` its end-to-end metrics. The last line on standard output
is the result as one JSON object; the last lines on standard error are the
numbers the correctness check compared, each beside its limit. Without a
TPU, or without the program's sources beside ``bench/``, it exits
non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"[bench] no program sources at {src}: run from a checkout "
              "of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from bench import harness
    try:
        cell = harness.load_cell(args.workload)
        out = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START)
    except (harness.HarnessError, FileNotFoundError) as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 1
    for name, c in out["compared"].items():
        print(f"[bench] compared {name}: {c['value']} (limit {c['rule']} "
              f"{c['limit']})", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
