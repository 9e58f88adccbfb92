"""Live scheduling benchmark: who owns the inference loop, and how well is
each dispatch sized to the live workload mix?

Three traffic scenarios, all driving the SAME paged engine configuration
(same model, same block pool, same ``max_batch``, same token budget)
through the AgentRM middleware — what changes per mode is the dispatch
discipline:

  * ``serialized-lanes`` — the pre-fusion design: thread-per-lane dispatch
    over ``SerializedPagedBackend`` (backend-wide lock per turn). Mixed
    scenario only; the paper's serialized baseline.
  * ``fused-budget`` — the fused iteration-level dispatcher over the
    budget megastep (DESIGN.md §11): per-step token budget, decode-first
    packing, variable-width prefill chunks, dispatch width drawn from the
    bounded pow2 bucket set, one dispatch per step.

Scenarios (token budgets are per-scenario knobs — right-sizing is the whole
point — but within a scenario every mode runs at equal hardware):

  * ``mixed``         — sub-chunk agent prompts interleave with sustained,
                        desynced decode against a throughput-tuned large
                        chunk; the budget right-sizes the dispatch width
                        to the live mix.
  * ``prefill-heavy`` — long prompts, near-empty batch, latency-tuned
                        small chunk: a prompt burns many chunks' worth of
                        budget in one dispatch.
  * ``decode-heavy``  — short prompts, long generations: mostly C == 1
                        steps, the prefill bursts fit the budget at a
                        right-sized width.

Timed regions end with ``engine.sync()`` (``jax.block_until_ready`` over
the KV pools) so async dispatch cannot flatter wall-clock numbers. TTFT and
inter-token latencies are sampled inside the engine (wall clock at each
output token, after the device->host transfer of the sampled ids). CAVEAT:
the engine's TTFT clock starts at engine admission (``submit``/``extend``),
so it measures prefill pacing only — middleware queueing (MLFQ wait, the
serialized backend's lock) is NOT included, and ``ttft_p95_ms`` is only
comparable across the engine-owned modes within a scenario, not a
full-stack first-token latency.

Reports per run: wall seconds, decoded tokens/sec, TTFT p95, P95
inter-token latency, ``padded_token_fraction``, trace buckets used vs the
bounded bucket set, ``jit_dispatches_per_step`` (must be 1.0), zombies
(must be 0). Emits ``BENCH_sched_live.json``. These are CPU runs: their
timings are no speed numbers (PERF.md holds the chip's).

    PYTHONPATH=src python -m benchmarks.sched_live [--smoke] [--check]

``--check`` is the CI smoke gate: non-zero exit if any fused run reaped a
zombie or failed a turn, if any run dispatched more than one jit call per
step, or if a run's distinct trace buckets exceeded its bounded pow2
bucket set (the recompile guard).
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import List, Optional

import numpy as np

SCENARIOS = {
    # prompt_repeat is the MAX prompt scale: agent i's prompt is the base
    # string repeated 1 + (i % prompt_repeat) times (capped at
    # prompt_tokens), so prompt lengths vary across agents and prefill
    # overlaps decode instead of the whole fleet phase-locking; budget is
    # every mode's per-step token budget
    # a throughput-tuned deployment runs a LARGE prefill chunk (128 here;
    # real Sarathi/vLLM chunks are 512+), but agent-turn prompts here are
    # sub-chunk (17-32 tokens). The budget (64 >= any prompt, so it almost
    # never rations) right-sizes C down to the pow2 bucket the live mix
    # actually needs (<= 32)
    "mixed": dict(agents=8, turns=2, new_tokens=10, jitter=8,
                  prompt_tokens=32, prompt_repeat=4, budget=64, chunk=128,
                  max_len=192),
    # long prompts against a near-EMPTY batch and a latency-tuned small
    # chunk (8): the budget lets a prompt burn the whole budget (24
    # chunks' worth) in one right-sized dispatch. Two desynced agents at
    # max_batch 2 keep the batch prefill-dominated
    "prefill-heavy": dict(agents=2, turns=2, new_tokens=2, jitter=2,
                          prompt_tokens=192, prompt_repeat=1,
                          prompt_scale=12, budget=192, chunk=8,
                          max_len=448, max_batch=2),
    # short prompts, long generations: mostly C == 1 steps; the prefill
    # bursts (8 rows x 8 tokens) fit the budget exactly at C == 8
    "decode-heavy": dict(agents=8, turns=1, new_tokens=24, prompt_tokens=8,
                         prompt_repeat=1, budget=64, chunk=16, max_len=192),
}


def _count_tokens(outs: List[str]) -> int:
    return sum(len(o.split(",")) for o in outs if o.startswith("tok:"))


def _drive(rm, eng, sc: dict, timeout: float = 600.0):
    """Submit `turns` rounds of one turn per agent (round n+1 extends the
    sessions round n parked); returns (wall_s, tokens, completed)."""
    scale = sc.get("prompt_scale", 1)
    # uncounted warmup turn: pays the session-path jit compiles (the
    # megastep trace buckets themselves are precompiled by
    # ``compile_buckets`` before this) so all modes measure steady-state
    rm.submit("warmup", "compile everything once, please " *
              (scale * sc["prompt_repeat"])).result(timeout)
    # reset EVERY reported counter after warmup so all columns describe
    # the same measurement window (buckets, dispatch ratios, padding,
    # latency samples). All engine stats live in the unified registry now
    # (DESIGN.md §12) — one reset covers counters, gauges and histograms
    eng.obs.metrics.reset()
    eng.obs.recorder.reset()
    eng.trace_buckets.clear()
    # every round is submitted up front — an agent's round-n+1 turn queues
    # behind its round-n turn (session_busy rotation), so agents desync and
    # prefill genuinely overlaps batchmates' decode instead of the whole
    # fleet phase-locking into all-prefill then all-decode waves
    t0 = time.perf_counter()
    handles = [rm.submit(f"agent{i}",
                         f"turn {turn} agent {i} — "
                         * (scale * (1 + i % sc["prompt_repeat"])))
               for turn in range(sc["turns"])
               for i in range(sc["agents"])]
    outs = [h.result(timeout) for h in handles]
    eng.sync()            # don't let async dispatch flatter the clock
    wall = time.perf_counter() - t0
    return wall, _count_tokens(outs), len(outs)


def run_mode(cfg, params, mode: str, sc: dict, *, max_batch: int,
             num_blocks: int, block_size: int, seed: int,
             budget: Optional[int], obs=None, mesh=None) -> dict:
    from repro.core import AgentRM, AgentRMConfig
    from repro.serving import (PagedEngineBackend, PagedInferenceEngine,
                               SerializedPagedBackend)

    max_batch = sc.get("max_batch", max_batch)   # scenario override: a
    # near-empty-batch scenario measures at the batch width it describes
    eng = PagedInferenceEngine(
        cfg, params, num_blocks=num_blocks, block_size=block_size,
        max_batch=max_batch, max_len=sc["max_len"],
        prefill_chunk=sc["chunk"], token_budget=budget, mesh=mesh, obs=obs)
    backend_cls = (SerializedPagedBackend if mode == "serialized-lanes"
                   else PagedEngineBackend)
    # every mode — including the serialized baseline — gets the exact same
    # workload knobs, or the cross-mode speedups would compare traffic
    backend = backend_cls(eng, max_new_tokens=sc["new_tokens"],
                          prompt_tokens=sc["prompt_tokens"],
                          new_tokens_jitter=sc.get("jitter", 0))
    # pay every megastep trace bucket's XLA compile up front — the bounded
    # bucket set is what makes this a finite, startup-time cost
    eng.compile_buckets()
    # generous detect_after: no mode should reap healthy turns that are
    # merely queued behind the backend lock / the decode batch
    rm = AgentRM(backend, AgentRMConfig(lanes=max_batch,
                                        detect_after_s=300.0, seed=seed))
    try:
        wall, tokens, completed = _drive(rm, eng, sc)
        snap = rm.monitor.snapshot()
        st = eng.step_stats()
        # engine-busy throughput: decoded tokens over summed in-step wall
        # time (the registry's engine.step_s histogram). Excludes the
        # dispatcher's idle waits and thread wakeups, so unlike wall-clock
        # tokens_per_s it is stable at CI sizes — the obs bench gates its
        # tracing-overhead contract on this
        busy = eng.h_step.sum
        return {
            "Method": mode,
            "wall_s": round(wall, 2),
            "tokens": tokens,
            "tokens_per_s": round(tokens / wall, 2),
            "engine_tokens_per_s": round(tokens / busy, 2) if busy else 0.0,
            # latency quantiles come from the unified registry's histograms;
            # the bounded reservoir keeps every sample at these run sizes,
            # so the quantile is exact (same numbers the old raw lists gave)
            "ttft_p95_ms": round(eng.h_ttft.quantile(0.95) * 1e3, 1),
            "itl_p95_ms": round(eng.h_itl.quantile(0.95) * 1e3, 1),
            "padded_token_fraction": round(st["padded_token_fraction"], 3),
            "trace_buckets": st["trace_buckets"],
            "bucket_set": st["bucket_set"],
            "decode_steps": eng.decode_steps,
            "jit_dispatches_per_step":
                round(st["jit_dispatches_per_step"], 2),
            "completed_turns": completed,
            "zombies": snap.zombies_reaped,
            "recoveries": snap.recoveries,
            # sharding columns: tp=1 outside a mesh; host transfer is the
            # per-step device->host traffic (one sampled int32 per row) and
            # must NOT grow with tp — logits reduce inside the dispatch
            "tp": st["tp"],
            "host_transfer_bytes_per_step":
                st["host_transfer_bytes_per_step"],
        }
    finally:
        rm.shutdown()


def sched_live(seed: int = 0, *, max_batch: int = 8, num_blocks: int = 193,
               block_size: int = 8, smoke: bool = False):
    import jax

    from repro.configs import get_smoke_config
    from repro.models import build

    cfg = get_smoke_config("gemma-2b").replace(remat=False)
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))

    scenarios = {k: dict(v) for k, v in SCENARIOS.items()}
    if smoke:
        for sc in scenarios.values():
            sc["agents"] = min(sc["agents"], 4)
            sc["turns"] = 1
            sc["new_tokens"] = min(sc["new_tokens"], 6)
        max_batch = 4

    results = {}
    for name, sc in scenarios.items():
        # serialized vs fused dispatch over one engine on mixed traffic;
        # the fused dispatcher alone on the skewed scenarios
        modes = (("serialized-lanes", "fused-budget") if name == "mixed"
                 else ("fused-budget",))
        # CPU wall clocks at these sizes are noisy: run each mode several
        # times and report the per-metric median (shape-derived metrics
        # like padded_token_fraction are identical across repeats anyway);
        # correctness counters (zombies, dispatches/step) take their worst
        # value so a regression in ANY repeat fails the check
        reps = 1 if smoke else 3
        rows = []
        for m in modes:
            runs = [run_mode(cfg, params, m, sc, max_batch=max_batch,
                             num_blocks=num_blocks, block_size=block_size,
                             seed=seed, budget=sc["budget"])
                    for _ in range(reps)]
            agg = dict(runs[0])
            for key in ("wall_s", "tokens_per_s", "engine_tokens_per_s",
                        "ttft_p95_ms", "itl_p95_ms",
                        "padded_token_fraction"):
                agg[key] = round(float(np.median([r[key] for r in runs])), 3)
            agg["zombies"] = max(r["zombies"] for r in runs)
            agg["jit_dispatches_per_step"] = max(
                r["jit_dispatches_per_step"] for r in runs)
            agg["trace_buckets"] = sorted(
                set().union(*[set(r["trace_buckets"]) for r in runs]))
            agg["completed_turns"] = min(r["completed_turns"] for r in runs)
            rows.append(agg)
        by = {r["Method"]: r for r in rows}
        summary = {}
        if "serialized-lanes" in by:
            summary["fused_speedup_tokens_per_s"] = round(
                by["fused-budget"]["tokens_per_s"]
                / max(by["serialized-lanes"]["tokens_per_s"], 1e-9), 2)
        results[name] = {"config": sc, "rows": rows, "summary": summary}

    payload = {
        "config": {"max_batch": max_batch, "num_blocks": num_blocks,
                   "block_size": block_size, "seed": seed, "smoke": smoke},
        "scenarios": results,
    }
    with open("BENCH_sched_live.json", "w") as f:
        json.dump(payload, f, indent=2)
    return results


def format_tables(results: dict) -> str:
    hdr = ["Method", "wall_s", "tokens_per_s", "ttft_p95_ms", "itl_p95_ms",
           "padded_token_fraction", "trace_buckets",
           "jit_dispatches_per_step", "completed_turns", "zombies"]
    out = []
    for name, res in results.items():
        out.append(f"### Live scheduling — {name} (equal hardware)")
        out.append("| " + " | ".join(hdr) + " |")
        out.append("|" + "---|" * len(hdr))
        for r in res["rows"]:
            out.append("| " + " | ".join(str(r[h]) for h in hdr) + " |")
        if res["summary"]:
            out.append("summary: " + ", ".join(
                f"{k}={v}x" for k, v in res["summary"].items()))
        out.append("")
    return "\n".join(out)


def check(results: dict, smoke: bool):
    """The CI gate: correctness invariants only (never wall-clock ratios —
    CPU CI boxes are too noisy for perf gates; the recorded JSON carries
    the ratios for the acceptance record)."""
    problems = []
    for name, res in results.items():
        sc = res["config"]
        expect = sc["agents"] * sc["turns"]
        for r in res["rows"]:
            tag = f"{name}/{r['Method']}"
            if r["Method"] != "serialized-lanes" and r["zombies"] != 0:
                problems.append(f"{tag} reaped {r['zombies']} zombies "
                                "(must stay 0)")
            if r["completed_turns"] != expect:
                problems.append(f"{tag} completed "
                                f"{r['completed_turns']}/{expect} turns")
            if r["jit_dispatches_per_step"] != 1.0:
                problems.append(
                    f"{tag} dispatched {r['jit_dispatches_per_step']} "
                    "jit calls per step (must be exactly 1)")
            # recompile guard: every dispatch width must come from the
            # bounded bucket set, so retraces stay <= len(bucket_set)
            extra = set(r["trace_buckets"]) - set(r["bucket_set"])
            if extra:
                problems.append(f"{tag} traced widths {sorted(extra)} "
                                f"outside bucket set {r['bucket_set']}")
            if len(r["trace_buckets"]) > len(r["bucket_set"]):
                problems.append(
                    f"{tag} used {len(r['trace_buckets'])} trace "
                    f"buckets > |bucket set| {len(r['bucket_set'])}")
    if problems:
        raise SystemExit("; ".join(problems))
    print("[sched_live] check passed: 0 zombies, all turns completed, "
          "1 jit dispatch per step, trace buckets "
          "within the bounded pow2 set")


# ----------------------------------------------------------------- chaos
# DESIGN.md §14: the chaos soak. Every sched_live scenario runs under a
# seeded FaultPlan (transient step faults, hangs, poisoned rows, KV
# squatting, swap IO errors + corruption, 429 bursts, engine crashes)
# with the full recovery stack armed: retry/backoff, watchdog deadline,
# KV-pressure degradation, write-ahead journal + rebuild. The gates are
# the blast-radius contract: 0 hangs, 0 zombies, 0 lost sessions, 0
# leaked KV blocks, every failure a typed EngineError — and with an
# EMPTY plan the chaos-instrumented stack is bitwise identical to the
# plain one. Emits ``BENCH_chaos.json``.

CHAOS_RATES = {
    "step_exception": 0.05, "step_hang": 0.01, "poison_row": 0.04,
    "kv_squat": 0.03, "swap_write_error": 0.02, "swap_read_error": 0.02,
    "swap_corrupt": 0.02, "rate_limit": 0.03, "crash": 0.01,
}


def _drive_chaos(rm, sc: dict, turns: int, timeout: float):
    """Submit every round up front (same desync pattern as ``_drive``);
    classify each turn's outcome instead of asserting success."""
    from repro.core.middleware import ZombieKilled
    from repro.serving.errors import EngineError

    handles = [(f"agent{i}",
                rm.submit(f"agent{i}",
                          f"turn {turn} agent {i} — "
                          * (sc.get("prompt_scale", 1)
                             * (1 + i % sc["prompt_repeat"]))))
               for turn in range(turns) for i in range(sc["agents"])]
    done = typed = untyped = zombies = hangs = 0
    for _, h in handles:
        try:
            out = h.result(timeout)
            assert out.startswith("tok:")
            done += 1
        except TimeoutError:
            hangs += 1              # the one unforgivable outcome
        except ZombieKilled:
            zombies += 1
        except EngineError:
            typed += 1
        except BaseException:  # noqa: BLE001 — anything else is a bug
            untyped += 1
    return {"turns_total": len(handles), "completed": done,
            "failed_typed": typed, "failed_untyped": untyped,
            "zombie_failures": zombies, "hangs": hangs}


def run_chaos_scenario(cfg, params, name: str, sc: dict, *, seed: int,
                       smoke: bool, journal_root: str) -> dict:
    import jax  # noqa: F401  (engines need an initialized backend)

    from repro.core import AgentRM, AgentRMConfig
    from repro.faults import ChaosBackend, FaultPlan, FaultyKVSwapStore
    from repro.obs import Observability
    from repro.serving import (PagedEngineBackend, PagedInferenceEngine,
                               SessionJournal)

    max_batch = sc.get("max_batch", 8 if not smoke else 4)
    obs = Observability()           # shared across rebuilds via the factory
    store = FaultyKVSwapStore()
    journal = SessionJournal(os.path.join(journal_root, name))
    # the soak runs MORE turns per retained session than the perf bench,
    # and adds a probe turn at the end — size max_len for that (a session
    # at capacity fails extend with a plain ValueError, which is a
    # workload-sizing mistake, not an injected fault) and give the pool
    # enough blocks that only the injector, never the workload itself,
    # creates hard exhaustion
    mult = 1 if smoke else 2
    turns = sc["turns"] * mult
    max_len = sc["max_len"] * (mult + 1)
    num_blocks = sc["agents"] * ((max_len + 7) // 8 + 1) + 9

    def factory():
        return PagedInferenceEngine(
            cfg, params, num_blocks=num_blocks, block_size=8,
            max_batch=max_batch, max_len=max_len,
            prefill_chunk=sc["chunk"], swap_store=store, obs=obs)

    engine = factory()
    engine.compile_buckets()
    inner = PagedEngineBackend(engine, max_new_tokens=sc["new_tokens"],
                               prompt_tokens=sc["prompt_tokens"],
                               new_tokens_jitter=sc.get("jitter", 0),
                               journal=journal, engine_factory=factory)
    plan = FaultPlan.generate(seed=seed + hash(name) % 1000, n_steps=5000,
                              rates=CHAOS_RATES, hang_s=0.4)
    chaos = ChaosBackend(inner, plan, store=store)
    # detect_after is generous so the WATCHDOG (not the reaper) owns hung
    # steps: a condemned-but-healthy turn would count as a zombie here
    rm = AgentRM(chaos, AgentRMConfig(lanes=max_batch, detect_after_s=300.0,
                                      seed=seed, step_backoff_s=0.01,
                                      step_deadline_s=20.0), obs=obs)
    chaos.on_rate_limit = rm.report_rate_limited
    timeout = 180.0 if smoke else 600.0
    t0 = time.perf_counter()
    try:
        row = _drive_chaos(rm, sc, turns, timeout)
        # lost-session probe: chaos off, every agent must still complete a
        # clean turn on its (possibly journal-restored) session. Disarm
        # one-shot store faults the plan loaded but nothing consumed yet —
        # they belong to the soak window, not the probe
        chaos.plan = FaultPlan()
        store.fail_next_put = store.fail_next_read = 0
        lost = 0
        for i in range(sc["agents"]):
            try:
                assert rm.submit(f"agent{i}",
                                 "probe turn").result(timeout) \
                    .startswith("tok:")
            except BaseException:  # noqa: BLE001
                lost += 1
        row["lost_sessions"] = lost
        row["zombies_reaped"] = rm.monitor.snapshot().zombies_reaped
    finally:
        rm.shutdown()
    # leak audit: drop the injector's hostage blocks and every retained
    # session — anything still allocated leaked
    chaos.release_squat()
    eng = inner.engine
    for rid in list(eng.reqs):
        eng.release(rid)
    row["leaked_blocks"] = eng.cache.allocator.num_used
    m = obs.metrics

    def c(n):
        cc = m.get(n)
        return int(cc.value) if cc is not None else 0

    row.update({
        "wall_s": round(time.perf_counter() - t0, 2),
        "injected": dict(chaos.injected),
        "step_retries": c("rm.step_retries"),
        "engine_rebuilds": c("rm.engine_rebuilds"),
        "kv_degradations": c("rm.kv_degradations"),
        "step_timeouts": c("rm.step_timeouts"),
        "rate_limit_events": c("rm.rate_limit_events"),
        "poisoned_rows": c("engine.poisoned_rows"),
        "swap_corruptions_injected": store.corruptions_injected,
        "swap_corruptions_detected": eng.swap.corruptions_detected,
        "swap_io_faults_fired": store.io_faults_fired,
        "journal_commits": journal.commits,
        "journal_skipped_corrupt": journal.skipped_corrupt,
    })
    return row


def _chaos_parity(cfg, params, sc: dict, *, smoke: bool) -> bool:
    """Faults disabled, instrumentation on: the ChaosBackend-wrapped stack
    must produce bitwise-identical tokens to the bare one."""
    from repro.core import AgentRM, AgentRMConfig
    from repro.faults import ChaosBackend, FaultPlan
    from repro.serving import PagedEngineBackend, PagedInferenceEngine

    max_batch = sc.get("max_batch", 8 if not smoke else 4)

    def run(wrap: bool):
        eng = PagedInferenceEngine(
            cfg, params, num_blocks=193, block_size=8,
            max_batch=max_batch, max_len=sc["max_len"],
            prefill_chunk=sc["chunk"])
        eng.compile_buckets()
        be = PagedEngineBackend(eng, max_new_tokens=sc["new_tokens"],
                                prompt_tokens=sc["prompt_tokens"],
                                new_tokens_jitter=sc.get("jitter", 0))
        rm = AgentRM(ChaosBackend(be, FaultPlan()) if wrap else be,
                     AgentRMConfig(lanes=max_batch, detect_after_s=300.0))
        try:
            hs = [rm.submit(f"agent{i}", f"parity turn {t} agent {i} — ")
                  for t in range(sc["turns"]) for i in range(sc["agents"])]
            return [h.result(300) for h in hs]
        finally:
            rm.shutdown()

    return run(False) == run(True)


def chaos_soak(seed: int = 0, smoke: bool = False) -> dict:
    import tempfile

    import jax

    from repro.configs import get_smoke_config
    from repro.models import build

    cfg = get_smoke_config("gemma-2b").replace(remat=False)
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(seed))

    scenarios = {k: dict(v) for k, v in SCENARIOS.items()}
    if smoke:
        for sc in scenarios.values():
            sc["agents"] = min(sc["agents"], 4)
            sc["turns"] = 1
            sc["new_tokens"] = min(sc["new_tokens"], 6)

    results = {}
    with tempfile.TemporaryDirectory(prefix="chaos-journal-") as jroot:
        for name, sc in scenarios.items():
            results[name] = run_chaos_scenario(cfg, params, name, sc,
                                               seed=seed, smoke=smoke,
                                               journal_root=jroot)
        # fleet arm: 3 engines under the same contract, plus the fleet
        # fault kinds (engine loss, migration interrupts, network delay)
        from benchmarks.fleet import fleet_chaos_row
        results["fleet"] = fleet_chaos_row(cfg, params, seed=seed,
                                           smoke=smoke, journal_root=jroot)
    parity = _chaos_parity(cfg, params, scenarios["mixed"], smoke=smoke)
    payload = {
        "config": {"seed": seed, "smoke": smoke, "rates": CHAOS_RATES},
        "scenarios": results,
        "parity_tokens_bitwise_identical": parity,
    }
    with open("BENCH_chaos.json", "w") as f:
        json.dump(payload, f, indent=2)
    return payload


def format_chaos(payload: dict) -> str:
    hdr = ["scenario", "turns_total", "completed", "failed_typed",
           "hangs", "zombie_failures", "lost_sessions", "leaked_blocks",
           "engine_rebuilds", "step_retries", "kv_degradations",
           "poisoned_rows", "wall_s"]
    out = ["### Chaos soak (seeded fault plan, DESIGN.md §14)",
           "| " + " | ".join(hdr) + " |", "|" + "---|" * len(hdr)]
    for name, r in payload["scenarios"].items():
        out.append("| " + " | ".join(
            str(r[h]) if h != "scenario" else name for h in hdr) + " |")
    out.append(f"parity (faults off, instrumentation on): "
               f"{payload['parity_tokens_bitwise_identical']}")
    return "\n".join(out)


def check_chaos(payload: dict):
    """The blast-radius contract, as a CI gate."""
    problems = []
    for name, r in payload["scenarios"].items():
        for key in ("hangs", "failed_untyped", "zombie_failures",
                    "lost_sessions", "leaked_blocks", "zombies_reaped"):
            if r[key] != 0:
                problems.append(f"{name}: {key}={r[key]} (must be 0)")
        if r["completed"] + r["failed_typed"] != r["turns_total"]:
            problems.append(
                f"{name}: {r['completed']} completed + "
                f"{r['failed_typed']} typed failures != "
                f"{r['turns_total']} turns")
    if not payload["parity_tokens_bitwise_identical"]:
        problems.append("chaos-instrumented tokens diverge from the plain "
                        "stack with faults disabled")
    if problems:
        raise SystemExit("; ".join(problems))
    print("[sched_live] chaos check passed: every turn completed or "
          "failed typed, 0 hangs / zombies / lost sessions / leaked "
          "blocks, bitwise parity with faults off")


# --------------------------------------------------------------- sharded
# DESIGN.md §13: the tensor-parallel megastep scaling curve. Runs on
# multi-device CPU by forcing virtual devices (XLA_FLAGS, set in main()
# BEFORE jax is imported — jax reads it at import time), so this bench is
# self-contained on any CI box. The model is a tiny f32 GQA config: f32
# because the parity oracle is exact token equality, and the psum's
# different reduction order costs a bf16 ulp per layer at tp>1 — enough to
# flip a greedy argmax even though the math is right (see DESIGN.md §13).

SHARDED_TPS = (1, 2, 4)


def _sharded_cfg():
    from repro.configs import get_smoke_config
    # hkv=4 shards across 4 virtual devices; g=2 (8 q heads over 4 kv
    # heads) exercises the tiled-GQA head permutation nontrivially
    return get_smoke_config("gemma-2b").replace(
        remat=False, n_layers=2, d_model=64, n_heads=8, n_kv_heads=4,
        head_dim=16, d_ff=128, vocab_size=256, compute_dtype="float32")


def parity_tokens(cfg, params, mesh) -> List[int]:
    """Engine-only deterministic two-turn drive (submit+retain, then
    extend): the greedy token ids are the parity oracle across meshes."""
    from repro.serving import PagedInferenceEngine

    eng = PagedInferenceEngine(cfg, params, num_blocks=65, block_size=8,
                               max_batch=4, max_len=96, prefill_chunk=16,
                               token_budget=16, mesh=mesh)
    rid = eng.submit(np.arange(1, 20, dtype=np.int32), max_new_tokens=8,
                     retain=True)
    eng.run_to_completion()
    toks = list(eng.reqs[rid].out_tokens)
    eng.extend(rid, np.arange(30, 38, dtype=np.int32), max_new_tokens=8)
    eng.run_to_completion()
    return toks + list(eng.reqs[rid].out_tokens)


def sharded_bench(seed: int = 0, smoke: bool = False) -> dict:
    import jax

    from repro.launch.mesh import make_tp_mesh
    from repro.models import build

    if jax.device_count() < max(SHARDED_TPS):
        raise SystemExit(
            f"sharded bench needs {max(SHARDED_TPS)} devices, found "
            f"{jax.device_count()} — run via `python -m "
            "benchmarks.sched_live --sharded` (main() forces virtual CPU "
            "devices before jax loads)")

    cfg = _sharded_cfg()
    params = build(cfg).init_params(jax.random.PRNGKey(seed))

    # ---- parity oracle: single-device vs every mesh width --------------
    ref = parity_tokens(cfg, params, None)
    parity = {"tokens_single": ref}
    for tp in SHARDED_TPS:
        toks = parity_tokens(cfg, params, make_tp_mesh(tp))
        parity[f"tp{tp}_tokens_equal"] = bool(toks == ref)

    # ---- scaling curve through the full middleware stack ---------------
    sc = dict(agents=4, turns=1 if smoke else 2, new_tokens=8, jitter=0,
              prompt_tokens=32, prompt_repeat=4, budget=64, chunk=16,
              max_len=192)
    rows = []
    for tp in (None,) + SHARDED_TPS:    # None = no mesh at all (baseline)
        mesh = make_tp_mesh(tp) if tp else None
        reps = 1 if smoke else 3
        runs = [run_mode(cfg, params, "fused-budget", sc, max_batch=4,
                         num_blocks=129, block_size=8, seed=seed,
                         budget=sc["budget"], mesh=mesh)
                for _ in range(reps)]
        agg = dict(runs[0])
        for key in ("wall_s", "tokens_per_s", "engine_tokens_per_s",
                    "ttft_p95_ms", "itl_p95_ms"):
            agg[key] = round(float(np.median([r[key] for r in runs])), 3)
        agg["zombies"] = max(r["zombies"] for r in runs)
        agg["jit_dispatches_per_step"] = max(
            r["jit_dispatches_per_step"] for r in runs)
        agg["trace_buckets"] = sorted(
            set().union(*[set(r["trace_buckets"]) for r in runs]))
        agg["completed_turns"] = min(r["completed_turns"] for r in runs)
        agg["Method"] = "single-device" if tp is None else f"mesh-tp{tp}"
        rows.append(agg)

    payload = {
        "config": {"seed": seed, "smoke": smoke,
                   "devices": jax.device_count(),
                   "model": {"n_layers": cfg.n_layers,
                             "n_heads": cfg.n_heads,
                             "n_kv_heads": cfg.n_kv_heads,
                             "compute_dtype": cfg.compute_dtype},
                   "scenario": sc},
        "parity": parity,
        "rows": rows,
    }
    with open("BENCH_sharded.json", "w") as f:
        json.dump(payload, f, indent=2)
    return payload


def format_sharded(payload: dict) -> str:
    hdr = ["Method", "tp", "wall_s", "tokens_per_s", "itl_p95_ms",
           "host_transfer_bytes_per_step", "trace_buckets",
           "jit_dispatches_per_step", "completed_turns", "zombies"]
    out = ["### Sharded megastep — scaling curve "
           f"({payload['config']['devices']} virtual CPU devices, f32)"]
    out.append("| " + " | ".join(hdr) + " |")
    out.append("|" + "---|" * len(hdr))
    for r in payload["rows"]:
        out.append("| " + " | ".join(str(r[h]) for h in hdr) + " |")
    flags = [f"tp{tp}={payload['parity'][f'tp{tp}_tokens_equal']}"
             for tp in SHARDED_TPS]
    out.append("parity vs single-device (exact token equality): "
               + ", ".join(flags))
    return "\n".join(out)


def check_sharded(payload: dict):
    """CI gate for the sharded bench: parity and structural invariants
    (never wall-clock ratios — virtual CPU devices time-slice one core, so
    the tokens/sec column is a record, not a gate)."""
    problems = []
    for tp in SHARDED_TPS:
        if not payload["parity"][f"tp{tp}_tokens_equal"]:
            problems.append(f"tp={tp} tokens diverged from single-device "
                            "(f32 parity oracle)")
    base = payload["rows"][0]["host_transfer_bytes_per_step"]
    for r in payload["rows"]:
        tag = f"sharded/{r['Method']}"
        if r["jit_dispatches_per_step"] != 1.0:
            problems.append(f"{tag} dispatched "
                            f"{r['jit_dispatches_per_step']} jit calls per "
                            "step (must be exactly 1)")
        if r["zombies"] != 0:
            problems.append(f"{tag} reaped {r['zombies']} zombies")
        if r["host_transfer_bytes_per_step"] != base:
            problems.append(
                f"{tag} host transfer {r['host_transfer_bytes_per_step']}B"
                f"/step != single-device {base}B/step — logits must reduce "
                "inside the dispatch")
        extra = set(r["trace_buckets"]) - set(r["bucket_set"])
        if extra:
            problems.append(f"{tag} traced widths {sorted(extra)} outside "
                            f"bucket set {r['bucket_set']}")
    if problems:
        raise SystemExit("; ".join(problems))
    print("[sched_live] sharded check passed: tp in "
          f"{list(SHARDED_TPS)} token-exact vs single-device, 1 jit "
          "dispatch per step, host transfer flat at "
          f"{base}B/step, 0 zombies, buckets within the pow2 set")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI (<=4 agents, 1 turn per "
                         "scenario)")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero on zombie/turn/dispatch/recompile "
                         "regression")
    ap.add_argument("--sharded", action="store_true",
                    help="run the tensor-parallel megastep scaling bench "
                         "on 4 forced virtual CPU devices; writes "
                         "BENCH_sharded.json")
    ap.add_argument("--chaos", action="store_true",
                    help="chaos soak: every scenario under a seeded fault "
                         "plan with the full recovery stack armed; writes "
                         "BENCH_chaos.json (gates with --check)")
    args = ap.parse_args()
    if args.sharded:
        # must land before ANY jax import (jax reads XLA_FLAGS at import
        # time) — everything above imports jax lazily for exactly this
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4")
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    if args.chaos:
        payload = chaos_soak(seed=args.seed, smoke=args.smoke)
        print(format_chaos(payload))
        print("[sched_live] wrote BENCH_chaos.json")
        if args.check:
            check_chaos(payload)
        return

    if args.sharded:
        payload = sharded_bench(seed=args.seed, smoke=args.smoke)
        print(format_sharded(payload))
        print("[sched_live] wrote BENCH_sharded.json")
        if args.check:
            check_sharded(payload)
        return

    results = sched_live(seed=args.seed, smoke=args.smoke)
    print(format_tables(results))
    print("[sched_live] wrote BENCH_sched_live.json")
    if args.check:
        check(results, args.smoke)


if __name__ == "__main__":
    import sys
    sys.path.insert(0, "src")
    main()
