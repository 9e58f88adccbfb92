"""Benchmark harness — one function per paper table. Prints the ours-vs-paper
tables and a machine-readable ``name,us_per_call,derived`` CSV summary.

    PYTHONPATH=src python -m benchmarks.run [--seed N] [--skip-roofline]
"""
from __future__ import annotations

import argparse
import sys

sys.path.insert(0, "src")

from benchmarks import context as ctx_bench
from benchmarks import scheduling as sched_bench


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--skip-roofline", action="store_true")
    ap.add_argument("--skip-paging", action="store_true",
                    help="skip the JAX paged-vs-dense engine scenario")
    ap.add_argument("--skip-sched-live", action="store_true",
                    help="skip the live fused-vs-serialized scheduling run")
    ap.add_argument("--skip-obs", action="store_true",
                    help="skip the tracing-overhead benchmark")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    csv_lines = ["name,us_per_call,derived"]

    print("=" * 72)
    print("AgentRM benchmarks — scheduling (paper Tables I-V)")
    print("=" * 72)
    for name, fn in [("normal", sched_bench.normal),
                     ("high_load", sched_bench.high_load),
                     ("burst", sched_bench.burst),
                     ("faulty", sched_bench.faulty),
                     ("cascade", sched_bench.cascade)]:
        rows, us = fn(seed=args.seed)
        print()
        print(sched_bench.format_table(name, rows))
        mlfq = next(r for r in rows if r["Method"] == "AgentRM-MLFQ")
        fifo = next(r for r in rows if r["Method"] == "FIFO")
        for r in rows:
            csv_lines.append(
                f"sched_{name}_{r['Method'].replace(' ', '_')},{us:.1f},"
                f"p95_ms={r['P95 (ms)']}")
        csv_lines.append(
            f"sched_{name}_p95_reduction,{us:.1f},"
            f"{1 - mlfq['P95 (ms)'] / max(fifo['P95 (ms)'], 1):.3f}")

    print()
    print("=" * 72)
    print("AgentRM benchmarks — context management (paper Tables VI-IX)")
    print("=" * 72)
    for name, fn in [("50_turn", ctx_bench.fifty_turn),
                     ("100_turn", ctx_bench.hundred_turn),
                     ("200_turn", ctx_bench.two_hundred_turn),
                     ("multi_topic", ctx_bench.multi_topic)]:
        rows, us = fn(seed=args.seed)
        print()
        print(ctx_bench.format_table(name, rows))
        for r in rows:
            csv_lines.append(
                f"ctx_{name}_{r['Method']},{us:.1f},"
                f"retention={r['retention']:.3f};quality={r['quality']:.2f};"
                f"cost={r['compact_cost']}")

    if not args.skip_paging:
        from benchmarks import paging as paging_bench
        print()
        print("=" * 72)
        print("AgentRM benchmarks — paged KV cache (dense vs paged serving)")
        print("=" * 72)
        rows, us = paging_bench.paging(seed=args.seed)
        print()
        print(paging_bench.format_table("hibernate_heavy", rows))
        dense = next(r for r in rows if r["Method"] == "dense-slots")
        paged = next(r for r in rows if r["Method"] == "paged-blocks")
        for r in rows:
            csv_lines.append(
                f"paging_{r['Method']},{us:.1f},"
                f"decode_ms={r['decode_ms']};hib_bytes={r['hib_bytes']};"
                f"peak_live={r['peak_live_tokens']}")
        csv_lines.append(
            f"paging_hib_bytes_reduction,{us:.1f},"
            f"{1 - paged['hib_bytes'] / max(dense['hib_bytes'], 1):.3f}")
        csv_lines.append(
            f"paging_live_ctx_gain,{us:.1f},"
            f"{paged['peak_live_tokens'] / max(dense['peak_live_tokens'], 1):.2f}x")
        print("\n[paging] wrote BENCH_paging.json")

    if not args.skip_sched_live:
        from benchmarks import sched_live as live_bench
        print()
        print("=" * 72)
        print("AgentRM benchmarks — live scheduling "
              "(serialized lanes vs fused MLFQ)")
        print("=" * 72)
        results = live_bench.sched_live(seed=args.seed)
        print()
        print(live_bench.format_tables(results))
        for scen, res in results.items():
            for r in res["rows"]:
                csv_lines.append(
                    f"sched_live_{scen}_{r['Method']},0.0,"
                    f"tokens_per_s={r['tokens_per_s']};"
                    f"zombies={r['zombies']};"
                    f"itl_p95_ms={r['itl_p95_ms']};"
                    f"padded={r['padded_token_fraction']};"
                    f"dispatches_per_step={r['jit_dispatches_per_step']}")
            for k, v in res["summary"].items():
                csv_lines.append(f"sched_live_{scen}_{k},0.0,{v}x")
        print("\n[sched_live] wrote BENCH_sched_live.json")

    if not args.skip_obs:
        from benchmarks import obs as obs_bench
        print()
        print("=" * 72)
        print("AgentRM benchmarks — observability "
              "(flight-recorder overhead + trace artifact)")
        print("=" * 72)
        payload = obs_bench.bench_obs(seed=args.seed)
        print(f"\n[obs] engine tokens/sec "
              f"off={payload['engine_tokens_per_s_off']} "
              f"on={payload['engine_tokens_per_s_on']} "
              f"ratio={payload['overhead_ratio']} "
              f"(floor {payload['overhead_floor']})")
        csv_lines.append(
            f"obs_tracing_overhead,0.0,"
            f"ratio={payload['overhead_ratio']};"
            f"engine_tokens_per_s_on={payload['engine_tokens_per_s_on']};"
            f"events={payload['trace']['events']};"
            f"dropped={payload['trace']['dropped']}")
        print(f"[obs] trace -> {payload['trace']['path']}; "
              "wrote BENCH_obs.json")

    if not args.skip_roofline:
        import os
        rdir = "reports/dryrun_v3" if os.path.isdir("reports/dryrun_v3") \
            else "reports/dryrun"
        if os.path.isdir(rdir) and os.listdir(rdir):
            from benchmarks import roofline
            print()
            print("=" * 72)
            print("Roofline (from dry-run artifacts; see EXPERIMENTS.md)")
            print("=" * 72)
            print(roofline.format_report(rdir))
            for r in roofline.interesting_cells(rdir):
                csv_lines.append(
                    f"roofline_{r['arch']}_{r['shape']},0.0,"
                    f"dominant={r['dominant']};frac={r['roofline_fraction']:.2f}")
        else:
            print("\n[roofline] no dry-run artifacts found — run "
                  "PYTHONPATH=src python -m repro.launch.dryrun first")

    print()
    print("=" * 72)
    print("CSV summary")
    print("=" * 72)
    for line in csv_lines:
        print(line)


if __name__ == "__main__":
    main()
