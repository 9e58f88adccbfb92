"""Reproduce, on the CPU, the fault that keeps the fleet cells out of the
benchmark: with more sessions than a fleet has rows, the router moves idle
sessions between engines through the swap path, and that path compiles
once per page count inside the measured window.

The run is the test-size fleet cell of ``test_bench_fleet.py`` (two
one-chip engines of four rows, on two host devices) with 16 sessions in
place of its 8. It prints the compiles in the window, the fleet's
migrations and the sessions per engine, and exits 1 while any compile
falls in the window, 0 once none does. Not a test: a reproducer for the
program fix, run as

    PYTHONPATH=src python3 tests/bench/fleet_migration_compiles.py [seed]
"""
import dataclasses
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(REPO, "tests", "bench", "data")
SESSIONS = 16
SECONDS = 4.0


def main(seed: int) -> int:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " --xla_"
                               "force_host_platform_device_count=2").strip()
    sys.path[:0] = [REPO, os.path.join(REPO, "src")]
    from bench import harness

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        e2e = [dict(m, workloads=["tiny.fleet"])
               for m in json.load(f)["end_to_end"]]
    spec = {"workloads": [{"name": "tiny.fleet", "config": "tiny",
                           "traffic": "tiny-fleet", "chips": 2,
                           "why": "reproducer"}],
            "end_to_end": e2e, "per_layer": []}
    cell = harness.load_cell("tiny.fleet", spec, roots=(DATA, harness.BENCH))
    cell = dataclasses.replace(cell, mix=dict(cell.mix, sessions=SESSIONS))
    reports = []
    log = harness.log

    def keep(msg):
        if msg.startswith("report "):
            reports.append(json.loads(msg[len("report "):]))
        log(msg)
    harness.log = keep
    out = harness.run_cell(cell, seed, SECONDS, False,
                           t_start=time.perf_counter(), require_tpu=False)
    rep = reports[-1]
    n = out["compared"]["compiles_in_window"]["value"]
    print(json.dumps({"compiles_in_window": n,
                      "fleet_migrations": rep["fleet_migrations"],
                      "sessions_by_member": rep["sessions_by_member"],
                      "swap_bytes_out": rep["swap_bytes_out"],
                      "correct": out["correct"]}), flush=True)
    return 1 if n else 0


if __name__ == "__main__":
    sys.exit(main(int(sys.argv[1]) if len(sys.argv) > 1 else 41))
