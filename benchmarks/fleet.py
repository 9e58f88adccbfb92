"""Elastic fleet benchmark: migration stalls, engine-loss recovery, and
the fleet-wide no-leak contract (DESIGN.md §15). Emits ``BENCH_fleet.json``.

Four arms:

  parity    faults off: a 3-engine fleet must produce bitwise-identical
            tokens to a single engine over the same multi-turn workload —
            the fleet layer adds routing, never arithmetic.
  fluid     a session decoding a long turn migrates engine-to-engine with
            pages streaming while it keeps serving tokens; gates are
            bit-exactness, zero leaked blocks on both engines, and the
            migrating session's ITL p95 during migration within 2x of its
            pre-migration p95 (floored — CPU CI timers are noisy).
  failover  one of two engines is killed mid-turn under a shared journal:
            in-flight turns on the corpse fail typed ``EngineLostError``,
            re-submitted turns restore bit-exactly on the survivor, and
            the recovery time (loss -> first displaced completion) is
            recorded.
  chaos     a 3-engine fleet under the full middleware with a seeded
            fault plan that includes fleet kinds (a guaranteed mid-soak
            ``engine_loss``, migration interrupts, network delays) on top
            of the single-engine chaos; gates are the blast-radius
            contract fleet-wide: 0 hangs / zombies / untyped failures /
            lost sessions / leaked blocks on surviving engines.

Like every bench here, ``--check`` gates structure and correctness, never
wall-clock (CPU CI boxes time-slice; timings are a record).
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import Dict, List, Optional

import numpy as np

FLEET_CHAOS_RATES = {
    "step_exception": 0.04, "step_hang": 0.0, "poison_row": 0.03,
    "kv_squat": 0.02, "swap_write_error": 0.015, "swap_read_error": 0.015,
    "swap_corrupt": 0.015, "rate_limit": 0.02, "crash": 0.008,
    "engine_loss": 0.003, "migration_interrupt": 0.02,
    "network_delay": 0.01,
}


def _quantile(xs: List[float], q: float) -> float:
    if not xs:
        return 0.0
    s = sorted(xs)
    return s[min(len(s) - 1, int(q * len(s)))]


def _drive(be, agents: Dict[str, str], max_steps: int = 600):
    """Direct drive (no middleware): one turn per agent, step to
    completion, classify outcomes."""
    rids = {be.begin_turn(a, "", p): a for a, p in agents.items()}
    outs, errs = {}, {}
    for _ in range(max_steps):
        if not rids:
            break
        rep = be.step()
        for rid, err in rep.failed:
            if rid in rids:
                errs[rids.pop(rid)] = err
        for rid in rep.finished:
            if rid in rids:
                outs[rids.pop(rid)] = be.collect(rid)
    assert not rids, f"turns never finished: {rids}"
    return outs, errs


def _release_all(engine) -> int:
    """Release every retained session; return blocks still allocated
    afterwards (the leak count)."""
    for rid in list(engine.reqs):
        engine.release(rid)
    return int(engine.cache.allocator.num_used)


def _mk_backend(cfg, params, *, name: str, journal=None, factory_kw=None,
                obs=None, max_new_tokens: int = 6, store=None):
    from repro.serving import PagedEngineBackend, PagedInferenceEngine

    kw = dict(num_blocks=48, block_size=8, max_batch=4, max_len=160,
              prefill_chunk=16)
    kw.update(factory_kw or {})

    def factory():
        return PagedInferenceEngine(cfg, params, name=name, obs=obs,
                                    swap_store=store, **kw)

    return PagedEngineBackend(factory(), max_new_tokens=max_new_tokens,
                              prompt_tokens=24, journal=journal,
                              engine_factory=(factory if journal is not None
                                              else None))


# ----------------------------------------------------------------- parity

def run_parity(cfg, params, *, turns: int, agents: int) -> dict:
    """Fleet-of-3 vs single engine, faults off, multi-turn: bitwise token
    parity. Placement spreads sessions across engines; each session's
    math never leaves its engine, so parity must hold exactly."""
    from repro.distributed.elastic import FleetBackend

    prompts = [{f"a{i}": f"parity turn {t} agent {i} — " * (1 + i % 3)
                for i in range(agents)} for t in range(turns)]
    single = _mk_backend(cfg, params, name="engine")
    ref = [_drive(single, p)[0] for p in prompts]
    fleet = FleetBackend([
        _mk_backend(cfg, params, name=f"engine{i}") for i in range(3)])
    got = [_drive(fleet, p)[0] for p in prompts]
    leaked = sum(_release_all(m.backend.engine) for m in fleet.members)
    return {"turns_total": turns * agents,
            "tokens_bitwise_identical": got == ref,
            "engines_used": len({h for h in fleet._home.values()}),
            "leaked_blocks": leaked}


# ------------------------------------------------------------------ fluid

def run_fluid(cfg, params, *, new_tokens: int) -> dict:
    """One long-decoding session fluid-migrates between two engines.
    Per-token wall-clock intervals are recorded before and during the
    migration window; the handoff stall and leak audit ride along."""
    from repro.distributed.elastic import FleetBackend

    prompt = "stream my pages while I decode " * 3
    single = _mk_backend(cfg, params, name="engine",
                         max_new_tokens=new_tokens)
    ref, _ = _drive(single, {"m": prompt})

    fleet = FleetBackend(
        [_mk_backend(cfg, params, name=f"engine{i}",
                     max_new_tokens=new_tokens) for i in range(2)],
        fluid_pages_per_tick=1, fluid_handoff_pages=2)
    # warm the TARGET engine's compile caches (prefill/decode buckets and
    # the swap gather/scatter paths) so the measured stall is migration
    # mechanics, not first-touch XLA compiles
    tgt = fleet.members[1].backend
    _drive(tgt, {"warm": prompt})
    tgt.hibernate_session("warm")
    tgt.wake_session("warm")
    tgt.evict_session("warm")

    ext = fleet.begin_turn("m", "", prompt)
    pre: List[float] = []
    during: List[float] = []
    migrated_at: Optional[int] = None
    outs: Dict[str, str] = {}
    last = time.perf_counter()
    for step in range(600):
        rep = fleet.step()
        now = time.perf_counter()
        if rep.serviced.get(ext):
            (during if migrated_at is not None else pre).append(now - last)
        last = now
        if migrated_at is None and len(pre) >= max(4, new_tokens // 4):
            assert fleet.migrate("m", 1, fluid=True), "fluid start refused"
            migrated_at = step
        if ext in rep.finished:
            outs["m"] = fleet.collect(ext)
            break
    mig = fleet.last_migration
    leaked = sum(_release_all(m.backend.engine) for m in fleet.members)
    pre_p95 = _quantile(pre, 0.95)
    dur_p95 = _quantile(during, 0.95)
    # CPU CI timers jitter at the millisecond scale; the floor keeps the
    # ratio meaningful when the absolute intervals are tiny
    ratio = dur_p95 / max(pre_p95, 0.05)
    return {"tokens_bitwise_identical": outs == ref,
            "migration_completed": bool(mig and mig.phase == "done"),
            "pages_streamed": int(mig.pages_sent if mig else 0),
            "handoff_stall_s": round(float(mig.stall_s or 0.0), 5)
            if mig else None,
            "pre_itl_p95_s": round(pre_p95, 5),
            "migration_itl_p95_s": round(dur_p95, 5),
            "itl_stall_ratio": round(ratio, 3),
            "leaked_blocks": leaked}


# --------------------------------------------------------------- failover

def run_failover(cfg, params, *, journal_root: str, agents: int) -> dict:
    """Two engines, one shared journal. Turn 1 lands sessions on both;
    turn 2 starts, then the busier engine is killed: its in-flight turns
    must fail typed ``EngineLostError``, and re-submitted turns must
    restore from the journal on the survivor bit-exactly against a
    no-kill reference run."""
    from repro.serving import EngineLostError, SessionJournal
    from repro.distributed.elastic import FleetBackend

    t1 = {f"f{i}": f"failover turn one agent {i} — " for i in range(agents)}
    t2 = {f"f{i}": f"failover turn two agent {i} — " for i in range(agents)}

    def build_fleet(tag: str):
        journal = SessionJournal(os.path.join(journal_root, tag))
        return FleetBackend(
            [_mk_backend(cfg, params, name=f"engine{i}", journal=journal)
             for i in range(2)], journal=journal)

    reference = build_fleet("ref")
    _drive(reference, t1)
    ref2, _ = _drive(reference, t2)

    fleet = build_fleet("kill")
    _drive(fleet, t1)
    homes = dict(fleet._home)
    victim = max(set(homes.values()),
                 key=lambda i: sum(1 for h in homes.values() if h == i))
    doomed = sorted(a for a, h in homes.items() if h == victim)

    rids = {fleet.begin_turn(a, "", p): a for a, p in t2.items()}
    for _ in range(2):
        rep = fleet.step()
        for rid in rep.finished:      # early finishers are fine
            if rid in rids:
                fleet.collect(rid)
                del rids[rid]
    assert fleet.kill_engine(victim)
    outs, errs = {}, {}
    kill_t: Optional[float] = None
    recovery_s: Optional[float] = None
    for _ in range(600):
        if not rids:
            break
        rep = fleet.step()
        if kill_t is None:
            kill_t = fleet.last_engine_loss_t
        for rid, err in rep.failed:
            if rid in rids:
                errs[rids.pop(rid)] = err
        for rid in rep.finished:
            if rid in rids:
                outs[rids.pop(rid)] = fleet.collect(rid)
    # every failed turn re-runs on the survivor via journal restore
    retry = {fleet.begin_turn(a, "", t2[a]): a for a in errs}
    for _ in range(600):
        if not retry:
            break
        rep = fleet.step()
        for rid in rep.finished:
            if rid in retry:
                a = retry.pop(rid)
                outs[a] = fleet.collect(rid)
                if recovery_s is None and a in doomed and kill_t is not None:
                    recovery_s = time.monotonic() - kill_t
    assert not retry, f"retried turns never finished: {retry}"
    leaked = sum(_release_all(m.backend.engine)
                 for m in fleet.members if m.alive)
    return {"turns_total": len(t2),
            "completed": len(outs),
            "failed_typed": sum(isinstance(e, EngineLostError)
                                for e in errs.values()),
            "failed_untyped": sum(not isinstance(e, EngineLostError)
                                  for e in errs.values()),
            "displaced_agents": len(doomed),
            "sessions_failed_over": fleet.fleet_stats()
            ["sessions_failed_over"],
            "turn2_bitwise_identical": outs == ref2,
            "recovery_s": round(recovery_s, 3)
            if recovery_s is not None else None,
            "leaked_blocks_alive_engines": leaked}


# ------------------------------------------------------------------ chaos

def fleet_chaos_row(cfg, params, *, seed: int, smoke: bool,
                    journal_root: str) -> dict:
    """A 3-engine fleet behind ``ChaosBackend`` and the full middleware,
    with fleet fault kinds live and one GUARANTEED mid-soak engine loss
    appended to the seeded plan (a rate-draw soak could roll zero losses
    and gate nothing). Same shape as a sched_live chaos row, so the
    sched_live --chaos table can carry a fleet arm."""
    from repro.core import AgentRM, AgentRMConfig
    from repro.faults import (ChaosBackend, FaultPlan, FaultSpec,
                              FaultyKVSwapStore)
    from repro.obs import Observability
    from repro.serving import SessionJournal
    from repro.distributed.elastic import FleetBackend
    from benchmarks.sched_live import _drive_chaos

    n_agents = 4 if smoke else 6
    turns = 2 if smoke else 4
    obs = Observability()
    journal = SessionJournal(os.path.join(journal_root, "fleet"))
    store = FaultyKVSwapStore()     # member 0's store hosts the IO faults
    members = []
    for i in range(3):
        members.append(_mk_backend(
            cfg, params, name=f"engine{i}", journal=journal, obs=obs,
            store=(store if i == 0 else None),
            factory_kw=dict(num_blocks=64, max_len=224)))
    fleet = FleetBackend(members, journal=journal,
                         fluid_pages_per_tick=2, fluid_handoff_pages=2)
    plan = FaultPlan.generate(seed=seed, n_steps=4000,
                              rates=FLEET_CHAOS_RATES, hang_s=0.3)
    # early enough that the smoke soak (a few dozen steps total) is still
    # mid-flight when the loss lands, late enough to be past the plan's
    # fault-free warmup window
    mid = 10 if smoke else 120
    plan = FaultPlan(list(plan.faults)
                     + [FaultSpec(mid, "engine_loss", float(seed))],
                     seed=seed)
    chaos = ChaosBackend(fleet, plan, store=store)
    rm = AgentRM(chaos, AgentRMConfig(lanes=8, detect_after_s=300.0,
                                      seed=seed, step_backoff_s=0.01,
                                      step_deadline_s=20.0), obs=obs)
    chaos.on_rate_limit = rm.report_rate_limited
    sc = {"agents": n_agents, "prompt_repeat": 3}
    t0 = time.perf_counter()
    try:
        row = _drive_chaos(rm, sc, turns, 240.0 if smoke else 600.0)
        # probe: chaos off, every session (including ones that lived on
        # the dead engine) completes a clean turn on a survivor
        chaos.plan = FaultPlan()
        store.fail_next_put = store.fail_next_read = 0
        lost = 0
        for i in range(n_agents):
            try:
                assert rm.submit(f"agent{i}", "probe turn") \
                    .result(240).startswith("tok:")
            except BaseException:  # noqa: BLE001
                lost += 1
        row["lost_sessions"] = lost
        row["zombies_reaped"] = rm.monitor.snapshot().zombies_reaped
    finally:
        rm.shutdown()
    chaos.release_squat()
    # leak audit covers SURVIVING engines: a dead member's pool died with
    # it (that is lost hardware, not a leak)
    row["leaked_blocks"] = sum(_release_all(m.backend.engine)
                               for m in fleet.members if m.alive)
    stats = fleet.fleet_stats()
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
        "kv_rebalances": c("rm.kv_rebalances"),
        # poisoned-row counters are namespaced per fleet member
        "poisoned_rows": sum(c(f"engine{i}.poisoned_rows")
                             for i in range(3)),
        "engines_lost": stats["engines_lost"],
        "engines_alive_end": sum(m.alive for m in fleet.members),
        "migrations_fluid": stats["migrations_fluid"],
        "migrations_sudden": stats["migrations_sudden"],
        "migrations_aborted": stats["migrations_aborted"],
        "sessions_failed_over": stats["sessions_failed_over"],
        "journal_commits": journal.commits,
    })
    return row


# ------------------------------------------------------------ entrypoints

def fleet_bench(seed: int = 0, smoke: bool = False) -> dict:
    import tempfile

    import jax

    from repro.configs import get_smoke_config
    from repro.models import build

    cfg = get_smoke_config("gemma-2b").replace(remat=False)
    params = build(cfg).init_params(jax.random.PRNGKey(seed))

    payload = {"config": {"seed": seed, "smoke": smoke,
                          "rates": FLEET_CHAOS_RATES}}
    payload["parity"] = run_parity(cfg, params,
                                   turns=1 if smoke else 2,
                                   agents=4 if smoke else 6)
    payload["fluid"] = run_fluid(cfg, params,
                                 new_tokens=24 if smoke else 48)
    with tempfile.TemporaryDirectory(prefix="fleet-journal-") as jroot:
        payload["failover"] = run_failover(cfg, params, journal_root=jroot,
                                           agents=3 if smoke else 5)
        payload["chaos"] = fleet_chaos_row(cfg, params, seed=seed,
                                           smoke=smoke, journal_root=jroot)
    with open("BENCH_fleet.json", "w") as f:
        json.dump(payload, f, indent=2)
    return payload


def format_fleet(payload: dict) -> str:
    p, fl, fo, ch = (payload["parity"], payload["fluid"],
                     payload["failover"], payload["chaos"])
    out = ["### Elastic fleet (DESIGN.md §15)"]
    out.append(f"parity: {p['turns_total']} turns over "
               f"{p['engines_used']} engines, bitwise identical to single "
               f"engine: {p['tokens_bitwise_identical']}, leaked blocks "
               f"{p['leaked_blocks']}")
    out.append(f"fluid migration: {fl['pages_streamed']} pages streamed "
               f"live, handoff stall {fl['handoff_stall_s']}s, ITL p95 "
               f"{fl['pre_itl_p95_s']}s -> {fl['migration_itl_p95_s']}s "
               f"(ratio {fl['itl_stall_ratio']}), bit-exact "
               f"{fl['tokens_bitwise_identical']}, leaked "
               f"{fl['leaked_blocks']}")
    out.append(f"failover: {fo['failed_typed']} typed engine-loss "
               f"failures, {fo['sessions_failed_over']} sessions failed "
               f"over, turn-2 bit-exact {fo['turn2_bitwise_identical']}, "
               f"recovery {fo['recovery_s']}s, leaked "
               f"{fo['leaked_blocks_alive_engines']}")
    out.append(f"chaos soak: {ch['completed']}/{ch['turns_total']} turns, "
               f"{ch['failed_typed']} typed, {ch['engines_lost']} engines "
               f"lost ({ch['engines_alive_end']} alive at end), "
               f"{ch['migrations_aborted']} migrations aborted, leaked "
               f"{ch['leaked_blocks']}, wall {ch['wall_s']}s")
    return "\n".join(out)


def check_fleet(payload: dict):
    """The fleet-wide blast-radius contract as a CI gate (structure and
    correctness only — never wall-clock)."""
    problems = []
    p = payload["parity"]
    if not p["tokens_bitwise_identical"]:
        problems.append("fleet tokens diverge from single-engine with "
                        "faults off")
    if p["leaked_blocks"]:
        problems.append(f"parity arm leaked {p['leaked_blocks']} blocks")
    fl = payload["fluid"]
    if not fl["migration_completed"]:
        problems.append("fluid migration never completed")
    if not fl["tokens_bitwise_identical"]:
        problems.append("fluid-migrated session's tokens diverge")
    if fl["leaked_blocks"]:
        problems.append(f"fluid arm leaked {fl['leaked_blocks']} blocks")
    if fl["itl_stall_ratio"] > 2.0:
        problems.append(f"migrating session ITL p95 ratio "
                        f"{fl['itl_stall_ratio']} > 2.0")
    fo = payload["failover"]
    if fo["failed_untyped"]:
        problems.append(f"failover: {fo['failed_untyped']} failures not "
                        "typed EngineLostError")
    if not fo["turn2_bitwise_identical"]:
        problems.append("failed-over sessions did not resume bit-exactly")
    if fo["leaked_blocks_alive_engines"]:
        problems.append(f"failover leaked "
                        f"{fo['leaked_blocks_alive_engines']} blocks")
    ch = payload["chaos"]
    for key in ("hangs", "failed_untyped", "zombie_failures",
                "lost_sessions", "leaked_blocks", "zombies_reaped"):
        if ch[key] != 0:
            problems.append(f"chaos: {key}={ch[key]} (must be 0)")
    if ch["completed"] + ch["failed_typed"] != ch["turns_total"]:
        problems.append(f"chaos: {ch['completed']} completed + "
                        f"{ch['failed_typed']} typed != "
                        f"{ch['turns_total']} turns")
    if ch["engines_lost"] < 1:
        problems.append("chaos: the guaranteed mid-soak engine loss "
                        "never fired")
    if problems:
        raise SystemExit("; ".join(problems))
    print("[fleet] check passed: fleet==single-engine parity, fluid "
          "migration bit-exact with bounded stall, engine loss fails "
          "typed and recovers bit-exactly, 0 leaked blocks fleet-wide")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny sizes for CI")
    ap.add_argument("--check", action="store_true",
                    help="exit non-zero on any fleet-contract violation")
    args = ap.parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    payload = fleet_bench(seed=args.seed, smoke=args.smoke)
    print(format_fleet(payload))
    print("[fleet] wrote BENCH_fleet.json")
    if args.check:
        check_fleet(payload)


if __name__ == "__main__":
    import sys
    sys.path.insert(0, "src")
    main()
