"""Production-traffic overload benchmark: the autopilot under fire.

Every other benchmark in this repo drives a CLOSED loop — submit a batch,
wait for it, measure. Real agent-app traffic is PARTLY OPEN: sessions
arrive when they arrive, whether or not the stack kept up (open loop
across sessions), but within a session the client is closed-loop — no
agent pipelines the tool-result turn behind an unanswered tool call, so
turn k+1 is only offered once turn k resolved. Both halves matter: the
open half is what makes overload possible at all, and the closed half
is what makes shedding effective (a purely open per-turn schedule fills
the queue with un-runnable session-serialized successor turns, and the
shed rung ends up rejecting the fresh runnable work instead of the
excess). Shed clients honor ``retry_after_s``: they re-offer the turn a
bounded number of times before giving up on it. This harness generates
that traffic (seeded, reproducible) and drives it through four dispatch
arms at equal hardware:

  * ``serialized``    — the historical thread-per-lane baseline over
                        ``SerializedPagedBackend``, on the same budgeted
                        engine as the other arms (record only).
  * ``static-budget`` — the fused budgeted megastep with every knob a
                        constant: no feedback, nothing sheds. Under
                        sustained overload its queue (and therefore its
                        first-token wait) grows WITHOUT BOUND — the gate
                        asserts the growth is monotonic across epochs.
  * ``autopilot``     — same engine + the SLO-feedback brownout ladder
                        (DESIGN.md §16): live token-budget retune within
                        the pre-traced pow2 buckets, hibernate, fleet
                        rebalance, and finally typed shedding with a
                        finite ``retry_after_s``. The gate: goodput stays
                        >= 0.9x measured single-arm capacity and the
                        completed-turn latency stays bounded while the
                        static arm's grows.
  * ``chaos``         — the autopilot arm under a seeded fault plan
                        (PR 8's injectors): the ladder must COMPOSE with
                        crash/rebuild, swap faults and 429 bursts —
                        0 hangs, 0 zombies, 0 leaked blocks.

Traffic model (all seeded ``random.Random``):
  * arrival processes — ``poisson`` (memoryless, the overload arms),
    ``burst`` (compound Poisson: periodic windows at several times the
    base rate — the chaos arm), ``diurnal`` (sinusoidally modulated rate
    via thinning — recorded in full runs).
  * heavy-tailed prompt lengths — Pareto-distributed body sizes, so most
    turns are short and a few drag entire prefill chunks.
  * sessions — every turn shares one SYSTEM_PROMPT prefix (the paged
    pool's prefix dedup and the fleet's prefix-affinity placement both
    key off it) and sessions are multi-turn: tool-call / tool-result
    bodies alternate on a retained session, the tool-heavy agent-app
    structure from ROADMAP #5.

The overload factor is calibrated, not guessed: a closed-loop run first
measures this box's single-arm capacity (turns/s through the full
middleware), then the open-loop schedule arrives at ``--factor`` (>= 3)
times that rate. CPU CI boxes differ wildly; calibration keeps "3x
overload" meaning 3x overload everywhere.

    PYTHONPATH=src python -m benchmarks.workload [--smoke] [--check]

Emits ``BENCH_overload.json``. ``--check`` is the CI gate described
above, plus: every shed is a typed ``BackpressureError`` with a finite
``retry_after_s``, no arm fails a turn untyped, and every arm's
distinct trace buckets stay within the pre-traced pow2 set (the
autopilot's live retuning must cause ZERO mid-run recompiles).
"""
from __future__ import annotations

import argparse
import json
import math
import random
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

SYSTEM_PROMPT = ("You are a coding agent. Tools: search(query), "
                 "read_file(path), write_file(path, text), bash(cmd). "
                 "Think, call one tool, await its result. ")

TOOL_CALLS = ("search", "read_file", "write_file", "bash")


# --------------------------------------------------------------- traffic
def poisson_arrivals(rng: random.Random, rate: float, n: int) -> List[float]:
    """Memoryless interarrivals at ``rate`` per second."""
    t, out = 0.0, []
    for _ in range(n):
        t += rng.expovariate(rate)
        out.append(t)
    return out


def burst_arrivals(rng: random.Random, rate: float, n: int, *,
                   burst_every_s: float = 2.0, burst_len_s: float = 0.5,
                   burst_factor: float = 6.0) -> List[float]:
    """Compound process: Poisson base load with periodic windows at
    ``burst_factor`` times the rate — the thundering-herd shape."""
    t, out = 0.0, []
    while len(out) < n:
        in_burst = (t % burst_every_s) < burst_len_s
        t += rng.expovariate(rate * (burst_factor if in_burst else 1.0))
        out.append(t)
    return out


def diurnal_arrivals(rng: random.Random, rate: float, n: int, *,
                     period_s: float = 20.0) -> List[float]:
    """Sinusoidally modulated Poisson via thinning: candidate events at
    2x rate, kept with probability tracking the phase of a 'day'."""
    t, out = 0.0, []
    while len(out) < n:
        t += rng.expovariate(2.0 * rate)
        keep = 0.5 * (1.0 + math.sin(2.0 * math.pi * t / period_s))
        if rng.random() < keep:
            out.append(t)
    return out


ARRIVAL_PROCESSES = {"poisson": poisson_arrivals, "burst": burst_arrivals,
                     "diurnal": diurnal_arrivals}


def heavy_tail_chars(rng: random.Random, base: int = 24,
                     alpha: float = 1.3, cap: int = 400) -> int:
    """Pareto(alpha) body length in characters: mostly short, occasional
    chunk-dragging whales. alpha < 2 keeps the variance honest."""
    return int(min(cap, base * rng.paretovariate(alpha)))


def turn_prompt(rng: random.Random, session: int, turn_idx: int) -> str:
    """Tool-heavy agent-app turn: tool calls and tool results alternate
    on the session, each with a heavy-tailed payload, all sharing the
    SYSTEM_PROMPT prefix so the pools' prefix dedup has something real
    to deduplicate."""
    body_chars = heavy_tail_chars(rng)
    tool = TOOL_CALLS[(session + turn_idx) % len(TOOL_CALLS)]
    if turn_idx % 2 == 0:
        body = (f"[turn {turn_idx}] call {tool}: "
                + "arg " * max(1, body_chars // 4))
    else:
        body = (f"[turn {turn_idx}] {tool} result: "
                + "data " * max(1, body_chars // 5))
    return SYSTEM_PROMPT + body[:body_chars + len(SYSTEM_PROMPT)]


def make_sessions(rng: random.Random, process: str, rate: float,
                  n_sessions: int, turns_per_session: int
                  ) -> List[Tuple[float, str, List[str]]]:
    """Partly-open traffic: session STARTS follow the arrival process at
    ``rate / turns_per_session`` (so aggregate turn demand is ``rate``),
    and each session is a closed-loop multi-turn tool conversation —
    turn k+1 is only offered once turn k resolved, the way a real agent
    client behaves (nobody pipelines a tool-result turn behind an
    unanswered tool call). Returns (arrival_s, session_id, prompts)."""
    sess_rate = rate / max(1, turns_per_session)
    times = ARRIVAL_PROCESSES[process](rng, sess_rate, n_sessions)
    return [(t, f"sess{i}",
             [turn_prompt(rng, i, k) for k in range(turns_per_session)])
            for i, t in enumerate(times)]


# ------------------------------------------------------------------ arms
def _engine_kw(n_sessions: int, turns_per_session: int, sc: dict) -> dict:
    """Pool sizing: enough blocks that only overload, never the workload
    itself, creates pressure (the chaos-soak sizing idiom)."""
    max_len = turns_per_session * (sc["prompt_tokens"]
                                   + sc["new_tokens"] + 4) + 32
    num_blocks = n_sessions * ((max_len + 7) // 8 + 1) + 17
    return dict(num_blocks=num_blocks, block_size=8,
                max_batch=sc["max_batch"], max_len=max_len,
                prefill_chunk=sc["chunk"])


def build_arm(arm: str, cfg, params, sc: dict, *, n_sessions: int,
              turns_per_session: int, seed: int, obs=None,
              chaos_plan=None, journal_root: Optional[str] = None):
    """One arm = engine + backend + middleware. Returns (rm, probe) where
    probe() resolves the CURRENT engine (chaos rebuilds swap it)."""
    from repro.core import AgentRM, AgentRMConfig
    from repro.obs import Observability
    from repro.serving import (PagedEngineBackend, PagedInferenceEngine,
                               SerializedPagedBackend)
    from repro.serving.autopilot import AutopilotConfig

    obs = obs or Observability()
    kw = _engine_kw(n_sessions, turns_per_session, sc)

    def make_engine():
        return PagedInferenceEngine(
            cfg, params, token_budget=sc["budget"], obs=obs, **kw)

    backend_kw = dict(max_new_tokens=sc["new_tokens"],
                      prompt_tokens=sc["prompt_tokens"])
    ap_cfg = None
    if arm in ("autopilot", "chaos"):
        ap_cfg = AutopilotConfig(
            slo_ttft_p95_s=sc["slo_ttft_s"], slo_itl_p95_s=sc["slo_itl_s"],
            window_s=2.0, min_samples=4, queue_high=sc["queue_high"],
            breach_passes=2, clear_passes=3, check_interval_s=0.05)

    rm_kw = dict(lanes=sc["max_batch"], detect_after_s=300.0, seed=seed,
                 autopilot=ap_cfg)
    if arm == "chaos":
        import os

        from repro.faults import ChaosBackend, FaultyKVSwapStore
        from repro.serving import SessionJournal

        store = FaultyKVSwapStore()
        journal = SessionJournal(os.path.join(journal_root, "chaos"))

        def factory():
            eng = PagedInferenceEngine(
                cfg, params, token_budget=sc["budget"],
                obs=obs, swap_store=store, **kw)
            return eng

        engine = factory()
        engine.compile_buckets()
        inner = PagedEngineBackend(engine, journal=journal,
                                   engine_factory=factory, **backend_kw)
        chaos = ChaosBackend(inner, chaos_plan, store=store)
        rm = AgentRM(chaos, AgentRMConfig(step_backoff_s=0.01,
                                          step_deadline_s=20.0, **rm_kw),
                     obs=obs)
        chaos.on_rate_limit = rm.report_rate_limited
        return rm, (lambda: inner.engine), chaos
    engine = make_engine()
    engine.compile_buckets()
    backend_cls = (SerializedPagedBackend if arm == "serialized"
                   else PagedEngineBackend)
    rm = AgentRM(backend_cls(engine, **backend_kw),
                 AgentRMConfig(**rm_kw), obs=obs)
    return rm, (lambda: engine), None


def drive_sessions(rm, engine_probe, sessions, *, timeout: float,
                   max_attempts: int = 4, retry_cap_s: float = 1.0) -> dict:
    """Partly-open driver: one client thread per session, started at the
    session's arrival time; WITHIN a session turns are closed-loop (turn
    k+1 is offered only after turn k resolved). A shed turn is retried
    after ``min(retry_after_s, retry_cap_s)`` up to ``max_attempts``
    offers — the well-behaved-client contract ``retry_after_s``
    advertises — and only then counted as a terminal shed; the session
    moves on to its next turn either way. A hang ends the session (a
    real client gives up), with the unreached turns counted
    ``not_attempted``.

    Completed-turn latencies (first offer -> completion, retry waits
    included: the user-perceived number) are split into three epochs by
    first-offer time across the ARRIVAL window only — drain-phase
    completions after the last session arrived say nothing about
    behavior under sustained overload, so the monotonic-growth /
    boundedness gates ignore them."""
    import threading

    from repro.core.middleware import ZombieKilled
    from repro.serving.errors import BackpressureError, EngineError

    t0 = time.perf_counter()
    records: List[list] = [[] for _ in sessions]

    def client(arrival: float, sess: str, prompts: List[str], rec: list):
        lag = arrival - (time.perf_counter() - t0)
        if lag > 0:
            time.sleep(lag)
        for k, prompt in enumerate(prompts):
            first_t = time.perf_counter() - t0
            for attempt in range(1, max_attempts + 1):
                try:
                    out = rm.submit(sess, prompt).result(timeout)
                    assert out.startswith("tok:")
                    rec.append(("completed", first_t,
                                time.perf_counter() - t0 - first_t))
                    break
                except BackpressureError as e:
                    ra = float(e.retry_after_s)
                    rec.append(("rejection", first_t, ra))
                    if attempt >= max_attempts:
                        rec.append(("shed", first_t, None))
                        break
                    finite = ra == ra and ra != float("inf") and ra > 0
                    time.sleep(min(ra, retry_cap_s)
                               if finite else retry_cap_s)
                except TimeoutError:
                    rec.append(("hang", first_t, None))
                    for _ in prompts[k + 1:]:
                        rec.append(("not_attempted", None, None))
                    return
                except ZombieKilled:
                    rec.append(("zombie", first_t, None))
                    break
                except EngineError as e:
                    rec.append(("typed:" + type(e).__name__, first_t, None))
                    break
                except BaseException as e:  # noqa: BLE001 — a bug, gated 0
                    rec.append(("untyped:" + type(e).__name__,
                                first_t, None))
                    break

    threads = [threading.Thread(target=client, args=(t, s, ps, rec),
                                daemon=True)
               for (t, s, ps), rec in zip(sessions, records)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    engine_probe().sync()
    wall = time.perf_counter() - t0

    completed = sheds = typed = untyped = zombies = hangs = 0
    rejections = not_attempted = 0
    latencies: List[Tuple[float, float]] = []   # (first-offer t, seconds)
    retry_afters: List[float] = []
    untyped_kinds: Dict[str, int] = {}
    typed_kinds: Dict[str, int] = {}
    for rec in records:
        for kind, first_t, val in rec:
            if kind == "completed":
                completed += 1
                latencies.append((first_t, val))
            elif kind == "rejection":
                rejections += 1
                retry_afters.append(val)
            elif kind == "shed":
                sheds += 1
            elif kind == "hang":
                hangs += 1
            elif kind == "not_attempted":
                not_attempted += 1
            elif kind == "zombie":
                zombies += 1
            elif kind.startswith("typed:"):
                typed += 1
                k = kind[len("typed:"):]
                typed_kinds[k] = typed_kinds.get(k, 0) + 1
            else:
                untyped += 1
                k = kind[len("untyped:"):]
                untyped_kinds[k] = untyped_kinds.get(k, 0) + 1

    window = sessions[-1][0] if sessions else 0.0
    epochs: List[Optional[float]] = []
    for lo, hi in ((0.0, 1 / 3), (1 / 3, 2 / 3), (2 / 3, 1.0 + 1e-9)):
        vals = [s for t, s in latencies
                if window > 0 and lo <= t / window < hi]
        epochs.append(round(float(np.mean(vals)), 4) if vals else None)
    n = sum(len(ps) for _, _, ps in sessions)
    return {
        "turns_total": n, "completed": completed, "sheds": sheds,
        "shed_rejections": rejections, "not_attempted": not_attempted,
        "failed_typed": typed, "failed_untyped": untyped,
        "typed_kinds": typed_kinds, "untyped_kinds": untyped_kinds,
        "zombie_failures": zombies, "hangs": hangs,
        "arrival_window_s": round(window, 2),
        "wall_s": round(wall, 2),
        "goodput_turns_per_s": round(completed / wall, 2) if wall else 0.0,
        "latency_epoch_means_s": epochs,
        "retry_after_min_s": (round(min(retry_afters), 3)
                              if retry_afters else None),
        "retry_after_max_s": (round(max(retry_afters), 3)
                              if retry_afters else None),
        "retry_after_all_finite": bool(all(
            r == r and r != float("inf") and r > 0 for r in retry_afters)),
    }


def measure_capacity(cfg, params, sc: dict, *, n_sessions: int,
                     turns_per_session: int, seed: int,
                     n_turns: int) -> float:
    """Bounded-concurrency closed-loop capacity: a sliding window of
    3x lanes outstanding turns through the full fused middleware at the
    ARMS' exact pool sizing. This is the healthy-operating-point
    yardstick 'Kx overload' is calibrated against — deliberately NOT a
    dump-everything closed loop, because this stack's per-pass dispatch
    cost grows with queue depth (that collapse is the failure mode the
    static arm demonstrates and the autopilot is supposed to prevent;
    baking it into the yardstick would hide it)."""
    rng = random.Random(seed + 1)
    rm, probe, _ = build_arm("static-budget", cfg, params, sc,
                             n_sessions=n_sessions,
                             turns_per_session=turns_per_session, seed=seed)
    inflight_cap = 3 * sc["max_batch"]
    try:
        rm.submit("warmup", SYSTEM_PROMPT + "compile the step").result(300)
        probe().obs.metrics.reset()
        probe().trace_buckets.clear()
        t0 = time.perf_counter()
        inflight: List[object] = []
        submitted = done = 0
        while done < n_turns:
            while submitted < n_turns and len(inflight) < inflight_cap:
                inflight.append(rm.submit(
                    f"cap{submitted % n_sessions}",
                    turn_prompt(rng, submitted % n_sessions,
                                submitted // n_sessions)))
                submitted += 1
            time.sleep(0.002)
            still = []
            for h in inflight:
                if h._done.is_set():
                    h.result(0)
                    done += 1
                else:
                    still.append(h)
            inflight = still
        probe().sync()
        wall = time.perf_counter() - t0
    finally:
        rm.shutdown()
    return n_turns / wall


# ------------------------------------------------------------- benchmark
def overload_bench(seed: int = 0, smoke: bool = False,
                   factor: float = 3.0) -> dict:
    import tempfile

    import jax

    from repro.configs import get_smoke_config
    from repro.faults import FaultPlan
    from repro.models import build

    cfg = get_smoke_config("gemma-2b").replace(remat=False)
    params = build(cfg).init_params(jax.random.PRNGKey(seed))

    # per-turn work (96 decoded tokens on a up-to-48-token prompt) is
    # sized so single-arm capacity lands in the ~5-15 turns/s band on a
    # CI CPU: the 3x-overload arrival window then spans several seconds,
    # many multiples of the ladder's escalation time (~0.5s of breached
    # passes) — a shed rung that only engages after the last arrival
    # sheds nothing, and the queue it was meant to bound is already deep
    sc = dict(max_batch=4, chunk=16, budget=32, prompt_tokens=48,
              new_tokens=96, queue_high=12, slo_ttft_s=2.0, slo_itl_s=0.5)
    turns_per_session = 4 if smoke else 5
    n_sessions = 36 if smoke else 120
    n_arrivals = n_sessions * turns_per_session
    rng = random.Random(seed)

    print("[workload] measuring single-arm capacity...", flush=True)
    capacity = measure_capacity(cfg, params, sc, n_sessions=n_sessions,
                                turns_per_session=turns_per_session,
                                seed=seed, n_turns=36 if smoke else 60)
    print(f"[workload] capacity {capacity:.2f} turns/s", flush=True)
    rate = factor * capacity

    results: dict = {}
    with tempfile.TemporaryDirectory(prefix="overload-journal-") as jroot:
        for arm, process in (("serialized", "poisson"),
                             ("static-budget", "poisson"),
                             ("autopilot", "poisson"),
                             ("chaos", "burst")):
            arm_sessions = n_sessions
            arm_tps = turns_per_session
            if arm == "serialized":     # record-only historical baseline:
                arm_sessions = min(n_sessions, 8)   # don't serialize the
                arm_tps = 2                         # whole overload window
            elif arm == "chaos":
                # the chaos gate is about typed-ness and leaks, not
                # throughput — half the storm bounds the runtime
                arm_sessions = max(8, n_sessions // 2)
            sessions = make_sessions(rng, process, rate, arm_sessions,
                                     arm_tps)
            plan = None
            if arm == "chaos":
                from benchmarks.sched_live import CHAOS_RATES

                # quarter-strength storm: sched_live's per-STEP rates are
                # calibrated for short (12-token) turns — at this arm's 96
                # new tokens per turn the full rates poison nearly every
                # turn and the bench degenerates into rebuild churn. The
                # gate is typed-ness + zero hangs/leaks under overload,
                # which needs a mixed outcome population, not a wipeout
                rates = {k: v * 0.25 for k, v in CHAOS_RATES.items()}
                plan = FaultPlan.generate(seed=seed + 7, n_steps=5000,
                                          rates=rates, hang_s=0.4)
            print(f"[workload] arm {arm}: {arm_sessions * arm_tps} turns / "
                  f"{arm_sessions} sessions arriving over "
                  f"{sessions[-1][0]:.1f}s ({process})", flush=True)
            rm, probe, chaos = build_arm(
                arm, cfg, params, sc, n_sessions=arm_sessions,
                turns_per_session=arm_tps, seed=seed,
                chaos_plan=plan, journal_root=jroot)
            try:
                row = drive_sessions(rm, probe, sessions,
                                     timeout=180.0 if smoke else 600.0)
                if rm.autopilot is not None:
                    row["autopilot"] = rm.autopilot.stats()
                m = rm.obs.metrics

                def c(name):
                    cc = m.get(name)
                    return int(cc.value) if cc is not None else 0

                row["admissions_shed_metric"] = c("rm.admissions_shed")
                row["zombies_reaped"] = rm.monitor.snapshot().zombies_reaped
            finally:
                rm.shutdown()
            eng = probe()
            if chaos is not None:
                # disarm before the audit: one-shot store faults the plan
                # loaded but nothing consumed belong to the storm window
                chaos.plan = FaultPlan()
                if chaos.store is not None:
                    chaos.store.fail_next_put = 0
                    chaos.store.fail_next_read = 0
                chaos.release_squat()
            st = eng.step_stats()
            row["trace_buckets"] = list(st["trace_buckets"])
            row["bucket_set"] = list(st["bucket_set"])
            row["jit_dispatches_per_step"] = round(
                st["jit_dispatches_per_step"], 2)
            # leak audit: drop every retained session — anything still
            # allocated leaked
            for rid in list(eng.reqs):
                eng.release(rid)
            row["leaked_blocks"] = eng.cache.allocator.num_used
            row["arrival_process"] = process
            results[arm] = row
            print(f"[workload] arm {arm} done: completed "
                  f"{row['completed']}/{row['turns_total']}, "
                  f"sheds {row['sheds']}, wall {row['wall_s']}s", flush=True)

    # the third generator is part of the traffic layer contract even when
    # no arm drives it: record its realized shape so regressions show
    d = diurnal_arrivals(random.Random(seed + 3), rate, 200)
    gaps = np.diff([0.0] + d)
    payload = {
        "config": {"seed": seed, "smoke": smoke, "factor": factor,
                   "capacity_turns_per_s": round(capacity, 2),
                   "overload_rate_turns_per_s": round(rate, 2),
                   "n_sessions": n_sessions, "n_arrivals": n_arrivals,
                   "turns_per_session": turns_per_session, "scenario": sc},
        "arms": results,
        "diurnal_generator": {
            "n": len(d), "mean_gap_s": round(float(np.mean(gaps)), 4),
            "cv_gap": round(float(np.std(gaps) / np.mean(gaps)), 2)},
    }
    with open("BENCH_overload.json", "w") as f:
        json.dump(payload, f, indent=2)
    return payload


def format_overload(payload: dict) -> str:
    hdr = ["arm", "arrival_process", "turns_total", "completed", "sheds",
           "shed_rejections", "failed_typed", "hangs", "zombie_failures",
           "leaked_blocks", "goodput_turns_per_s", "latency_epoch_means_s",
           "wall_s"]
    cfgrow = payload["config"]
    out = [f"### Overload autopilot — {cfgrow['factor']}x sustained "
           f"overload (capacity {cfgrow['capacity_turns_per_s']} turns/s, "
           f"{cfgrow['n_sessions']} sessions)",
           "| " + " | ".join(hdr) + " |", "|" + "---|" * len(hdr)]
    for arm, r in payload["arms"].items():
        out.append("| " + " | ".join(
            str(r.get(h)) if h != "arm" else arm for h in hdr) + " |")
    ap = payload["arms"]["autopilot"]
    out.append(
        f"autopilot: goodput {ap['goodput_turns_per_s']}/"
        f"{cfgrow['capacity_turns_per_s']} turns/s, "
        f"{ap['shed_rejections']} shed rejections / {ap['sheds']} turns "
        f"given up (retry_after "
        f"[{ap['retry_after_min_s']}, {ap['retry_after_max_s']}]s), "
        f"final rung {ap.get('autopilot', {}).get('rung')}")
    return "\n".join(out)


def check_overload(payload: dict):
    """The acceptance gates, as a CI exit code."""
    problems = []
    cfgrow = payload["config"]
    arms = payload["arms"]
    for arm, r in arms.items():
        for key in ("hangs", "failed_untyped", "zombie_failures",
                    "zombies_reaped", "leaked_blocks"):
            if r[key] != 0:
                problems.append(f"{arm}: {key}={r[key]} (must be 0)")
        outcomes = (r["completed"] + r["sheds"] + r["failed_typed"]
                    + r["failed_untyped"] + r["zombie_failures"]
                    + r["hangs"] + r["not_attempted"])
        if outcomes != r["turns_total"]:
            problems.append(f"{arm}: outcomes sum to {outcomes}, not "
                            f"{r['turns_total']} turns")
        extra = set(r["trace_buckets"]) - set(r["bucket_set"])
        if extra:
            problems.append(
                f"{arm}: traced widths {sorted(extra)} outside the "
                f"pre-traced set {r['bucket_set']} (mid-run recompile)")
    static, ap = arms["static-budget"], arms["autopilot"]
    if static["shed_rejections"] != 0:
        problems.append("static-budget arm shed turns without an autopilot")
    s_epochs = static["latency_epoch_means_s"]
    if None in s_epochs or not (s_epochs[0] < s_epochs[1] < s_epochs[2]):
        problems.append(
            f"static-budget latency epochs {s_epochs} are not "
            "monotonically growing — the overload is not sustained "
            "enough to demonstrate the unbounded-queue failure mode")
    goodput_ratio = (ap["goodput_turns_per_s"]
                     / max(cfgrow["capacity_turns_per_s"], 1e-9))
    if goodput_ratio < 0.9:
        problems.append(
            f"autopilot goodput {ap['goodput_turns_per_s']} turns/s is "
            f"{goodput_ratio:.2f}x capacity (must stay >= 0.9x)")
    if ap["shed_rejections"] < 1:
        problems.append("autopilot arm never shed under "
                        f"{cfgrow['factor']}x overload — the ladder "
                        "never reached the shed rung")
    if ap["shed_rejections"] >= 1 and ap["retry_after_max_s"] is not None \
            and not (0 < ap["retry_after_max_s"] <= 30.0):
        problems.append(
            f"shed retry_after max {ap['retry_after_max_s']}s outside "
            "the promised (0, 30] window")
    if not ap["retry_after_all_finite"]:
        problems.append("a shed BackpressureError carried a non-finite "
                        "or non-positive retry_after_s")
    a_epochs = ap["latency_epoch_means_s"]
    if a_epochs[2] is not None and s_epochs[2] is not None \
            and a_epochs[2] >= s_epochs[2]:
        problems.append(
            f"autopilot final-epoch latency {a_epochs[2]}s did not beat "
            f"the static arm's {s_epochs[2]}s — the ladder bounded "
            "nothing")
    if problems:
        raise SystemExit("; ".join(problems))
    print("[workload] check passed: static TTFT grows monotonically "
          f"{s_epochs}, autopilot goodput {goodput_ratio:.2f}x capacity "
          f"with bounded latency {a_epochs} and {ap['shed_rejections']} "
          "typed shed rejections (finite retry_after), trace buckets "
          "within the "
          "pre-traced set, chaos arm 0 hangs/zombies/leaks")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="CI sizes: shorter overload window, fewer "
                         "sessions")
    ap.add_argument("--factor", type=float, default=3.0,
                    help="overload factor vs measured capacity (>= 3 for "
                         "the acceptance gates)")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero if any overload gate fails")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    payload = overload_bench(seed=args.seed, smoke=args.smoke,
                             factor=args.factor)
    print(format_overload(payload))
    print("[workload] wrote BENCH_overload.json")
    if args.check:
        check_overload(payload)


if __name__ == "__main__":
    import sys
    sys.path.insert(0, "src")
    main()
