#!/usr/bin/env python3
"""Chip smoke: serve chatglm3-6b at full width on one TPU through the
normal path, and check what comes out.

    python3 chip_smoke.py               # one chip (what CI on a TPU runs)
    python3 chip_smoke.py --four-chips  # the four-chip paths only

One chip: chatglm3-6b at its published widths and all 28 layers, random
bf16 weights from ``--seed``, behind ``repro.launch.serve.build_backend``
(``--paged --token-budget 256``) and ``AgentRM``. Every megastep bucket is
compiled, then a few multi-turn agent sessions are served with prompts of
a few hundred to about 2k tokens; one session is hibernated and woken
between its turns. Before that, the paged-attention kernel is checked on
the chip against the jnp reference at the model's head widths.

Four chips (``--four-chips``), and nothing else:
  (a) four chatglm3-6b fleet members (published widths, depth cut so one
      chip holds a whole f32 copy), each with its params, pools and
      megastep on its own chip; one fluid migration and one engine kill
      happen mid-traffic, and every turn's tokens must equal a single
      engine's for the same requests, bit for bit, in f32;
  (b) the tensor-parallel megastep at tp=4 on starcoder2-7b (published
      widths, depth cut so one chip holds a whole f32 copy) must produce
      the tp=1 engine's greedy tokens exactly, in f32.

The script runs in one process that owns every chip it uses. It exits
non-zero, and prints no result line, when JAX finds no TPU, when it is not
run from the repository, or when any check fails. On success its last line
is ``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH = "chatglm3-6b"
SERVE_ARGS = ["--arch", ARCH, "--paged", "--token-budget", "256",
              "--max-batch", "4", "--max-len", "3072", "--num-blocks", "1024",
              "--block-size", "16", "--max-new-tokens", "16"]
PROMPT_TOKENS = 2048            # the backend's byte-tokenizer cap
# (agent, prompt length per turn); agent-2 hibernates between its turns
SESSIONS = {"agent-0": (2000, 300, 150), "agent-1": (1200, 400),
            "agent-2": (600, 250), "agent-3": (300, 800)}
HIBERNATE = "agent-2"
# Kernel check, f32 at "highest" matmul precision: the kernel's online
# softmax and the reference's two-pass softmax differ only by f32
# rounding, about 1e-6 on outputs of magnitude ~1 over 2k keys. Rounding
# the same inputs to bf16 moves the outputs by about 1e-2. 1e-4 sits two
# decades from each, so it passes f32 and fails any bf16 shortcut (the
# script checks that the bf16 control does fail it).
KERNEL_ATOL = 1e-4
# The four-chip checks compare exact greedy tokens, in f32, at a depth cut
# that one chip holds in f32 (chatglm3-6b: 8 of 28 layers, 8.7 GB). At
# bf16 a row's rounding depends on the bucket width of the step it rides
# in, and a fleet packs rows into different steps than one engine does:
# on a TPU v5e the bf16 fleet flipped a near-tied greedy token in 2 of 8
# turns against one engine at full depth.
FLEET_LAYERS = 8
TP_ARCH, TP_LAYERS = "starcoder2-7b", 4


def log(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


def prompt(rng, n: int) -> str:
    """A seeded printable prompt of ``n`` bytes (= n byte tokens)."""
    letters = "abcdefghijklmnopqrstuvwxyz ,.:;()=+-_"
    return "".join(rng.choice(list(letters), size=n))


def kernel_check(problems: list):
    """The Pallas kernel on the chip vs ``paged_prefill_attention_ref``
    (g-major pairing) at chatglm3-6b head widths, f32, ragged rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels.paged_attention.kernel import \
        paged_prefill_attention_bcd
    from repro.kernels.paged_attention.ref import paged_prefill_attention_ref

    cfg = get_config(ARCH)
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    b, C, blk, npages = 4, 256, 16, 128
    nb = b * npages + 1
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, C, hq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((nb, hkv, blk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((nb, hkv, blk, d)), jnp.float32)
    ids = rng.permutation(np.arange(1, nb))[: b * npages].reshape(b, npages)
    tables = jnp.asarray(ids, jnp.int32)
    cache = jnp.asarray([1500, 37, 0, 900], jnp.int32)    # prefill, decode,
    valids = jnp.asarray([C, 1, 200, 0], jnp.int32)       # fresh, inactive
    live = np.asarray(valids)[:, None] > np.arange(C)[None, :]
    with jax.default_matmul_precision("highest"):
        out = paged_prefill_attention_bcd(q, k, v, cache, valids, tables)
        ref = paged_prefill_attention_ref(q, k, v, cache, valids, tables,
                                          pairing="g_major")
        r16 = [jnp.asarray(x, jnp.bfloat16).astype(jnp.float32)
               for x in (q, k, v)]
        ctl = paged_prefill_attention_ref(*r16, cache, valids, tables,
                                          pairing="g_major")
    out, ref, ctl = (np.asarray(x)[live] for x in (out, ref, ctl))
    err = float(np.abs(out - ref).max())
    ctl_err = float(np.abs(ctl - ref).max())
    log(f"kernel vs reference (hq {hq}, hkv {hkv}, d {d}, blk {blk}, C {C}, "
        f"f32, highest): max abs err {err:.3e}, tolerance {KERNEL_ATOL:.0e}; "
        f"bf16-rounded control err {ctl_err:.3e}")
    if not err <= KERNEL_ATOL:
        problems.append(f"kernel error {err:.3e} > {KERNEL_ATOL:.0e}")
    if not ctl_err > KERNEL_ATOL:
        problems.append("the bf16 control passes the kernel tolerance: it "
                        "is too loose to tell f32 from bf16")


def serve_turns(rm, plan, problems: list, *, hibernate=None, on_wave=None):
    """Submit ``plan`` (agent -> prompt texts) turn by turn through
    ``AgentRM``; every turn must finish with its full token count and no
    failed sentinel. A turn that fails with a lost engine is resubmitted
    once (the fleet restores its session from the journal). Returns
    {agent: [token lists per turn]}."""
    from repro.serving import EngineLostError

    outs = {a: [] for a in plan}
    waves = max(len(p) for p in plan.values())
    for w in range(waves):
        if w == 1 and hibernate is not None:
            rm.hibernate_agent(hibernate)
            rm.wake_agent(hibernate)
            log(f"{hibernate} hibernated and woken between its turns")
        handles = {a: rm.submit(a, p[w]) for a, p in plan.items()
                   if w < len(p)}
        if on_wave is not None:
            on_wave(w)
        for a, h in handles.items():
            try:
                out = h.result(timeout=600)
            except EngineLostError:
                out = rm.submit(a, plan[a][w]).result(timeout=600)
            toks = [int(t) for t in out.split(":", 1)[1].split(",") if t]
            outs[a].append(toks)
    for a, turns in outs.items():
        for i, toks in enumerate(turns):
            if not toks or min(toks) < 0:
                problems.append(f"{a} turn {i}: bad tokens {toks[:4]}")
    return outs


def megastep_has_kernel(engine) -> bool:
    """Whether the compiled C == 1 megastep holds the Pallas kernel."""
    import jax.numpy as jnp
    b = engine.max_batch
    compiled = engine._mega.lower(
        engine.params, engine.cache.pools(), jnp.zeros((b, 1), jnp.int32),
        jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
        jnp.zeros((b, engine.max_pages), jnp.int32),
        jnp.zeros((b,), jnp.bool_)).compile()
    return "tpu_custom_call" in compiled.as_text()


def counters_clean(obs, problems: list):
    m = obs.metrics
    for name in ("rm.step_retries", "rm.engine_rebuilds", "rm.step_timeouts"):
        c = m.get(name)
        if c is not None and c.value:
            problems.append(f"{name} = {int(c.value)}: a step failed")


def one_chip(seed: int, problems: list):
    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.core import AgentRM, AgentRMConfig
    from repro.launch.serve import (build_backend, build_parser,
                                    init_serving_params)
    from repro.obs import Observability

    kernel_check(problems)
    dev = jax.devices()[0]
    t0 = time.perf_counter()
    cfg, params = init_serving_params(get_config(ARCH).replace(remat=False),
                                      seed)
    jax.block_until_ready(params)
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads}, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab_size}; params {nbytes} bytes ({cfg.param_dtype}), "
        f"init {time.perf_counter() - t0:.1f}s")
    args = build_parser().parse_args(SERVE_ARGS)
    obs = Observability()
    t0 = time.perf_counter()
    engine, backend = build_backend(cfg, params, args, obs=obs)
    log(f"compile seconds per bucket: "
        + ", ".join(f"C={c}: {s:.2f}" for c, s in
                    sorted(engine.compile_seconds.items()))
        + f" (total {time.perf_counter() - t0:.1f}s)")
    log(f"engine device {engine.device.platform}:{engine.device.id}, "
        f"paged kernel {engine.paged_kernel}, block {args.block_size}, "
        f"blocks {args.num_blocks}, max_len {engine.max_len}, "
        f"buckets {list(engine.bucket_set)}")
    if not engine.paged_kernel:
        problems.append("the engine did not choose the paged kernel")
    backend.prompt_tokens = PROMPT_TOKENS
    rm = AgentRM(backend, AgentRMConfig(lanes=args.max_batch,
                                        detect_after_s=120.0), obs=obs)
    rng = np.random.default_rng(seed)
    plan = {a: [prompt(rng, n) for n in lens]
            for a, lens in SESSIONS.items()}
    t0 = time.perf_counter()
    try:
        outs = serve_turns(rm, plan, problems, hibernate=HIBERNATE)
    finally:
        rm.shutdown()
    wall = time.perf_counter() - t0
    counters_clean(obs, problems)
    snap = rm.monitor.snapshot()
    if snap.zombies_reaped:
        problems.append(f"{snap.zombies_reaped} turns reaped as zombies")
    turns = sum(len(t) for t in outs.values())
    want = sum(len(t) for t in SESSIONS.values())
    tokens = sum(len(t) for ts in outs.values() for t in ts)
    full = all(len(t) == args.max_new_tokens for ts in outs.values()
               for t in ts)
    st = engine.step_stats()
    kv = engine.kv_stats()
    log(f"turns completed {turns}/{want}, tokens generated {tokens}, "
        f"every turn full length {full}, serving wall {wall:.1f}s")
    log(f"jit_dispatches_per_step {st['jit_dispatches_per_step']}, "
        f"steps {st['steps_dispatched']}, trace buckets "
        f"{st['trace_buckets']}, padded fraction "
        f"{st['padded_token_fraction']:.3f}, swap out/in bytes "
        f"{kv.get('swap_bytes_out')}/{kv.get('swap_bytes_in')}")
    log("host-clock latency (s): step p50 {:.4f} p95 {:.4f}, ttft p50 {:.4f} "
        "p95 {:.4f}, itl p50 {:.4f} p95 {:.4f}".format(
            *(h.quantile(q) or 0.0 for h in (engine.h_step, engine.h_ttft,
                                             engine.h_itl)
              for q in (0.5, 0.95))))
    has_kernel = megastep_has_kernel(engine)
    log(f"tpu_custom_call in the megastep HLO: {has_kernel}")
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    log(f"peak_bytes_in_use {peak}")
    if turns != want or not full:
        problems.append(f"{turns}/{want} turns, full length {full}")
    if st["jit_dispatches_per_step"] != 1.0:
        problems.append("megastep ran more than one dispatch per step")
    if not has_kernel:
        problems.append("no tpu_custom_call in the megastep HLO")
    if not kv.get("swap_bytes_out"):
        problems.append(f"{HIBERNATE} never swapped out")


def fleet_phase(seed: int, problems: list):
    """(a) four fleet members, one per chip, vs one engine: same tokens."""
    import numpy as np

    from repro.configs import get_config
    from repro.core import AgentRM, AgentRMConfig
    from repro.launch.serve import (build_backend, build_parser,
                                    init_serving_params)
    from repro.obs import Observability

    full = get_config(ARCH)
    cfg = full.replace(remat=False, n_layers=FLEET_LAYERS,
                       compute_dtype="float32")
    log(f"fleet phase: {ARCH} at published widths, depth cut {FLEET_LAYERS} "
        f"of {full.n_layers} layers, f32, highest precision")
    cfg, params = init_serving_params(cfg, seed)
    rng = np.random.default_rng(seed)
    plan = {f"agent-{i}": [prompt(rng, n) for n in (500 + 300 * i, 1200)]
            for i in range(4)}

    def run(extra, on_wave=None, fleet_out=None):
        # the single engine holds all four sessions at once: 8.7k tokens
        # of history, 546 pages of 16 (the 1024 of SERVE_ARGS hold them)
        args = build_parser().parse_args(SERVE_ARGS + extra)
        obs = Observability()
        engine, backend = build_backend(cfg, params, args, obs=obs)
        members = getattr(backend, "members", None)
        for be in ([m.backend for m in members] if members else [backend]):
            be.prompt_tokens = PROMPT_TOKENS
        if fleet_out is not None:
            fleet_out.append(backend)
        rm = AgentRM(backend, AgentRMConfig(lanes=args.max_batch * args.fleet,
                                            detect_after_s=120.0), obs=obs)
        try:
            outs = serve_turns(rm, plan, problems, on_wave=on_wave)
        finally:
            rm.shutdown()
        counters_clean(obs, problems)
        return outs, backend

    ref, ref_backend = run([])
    log(f"single engine on {ref_backend.engine.device}: "
        f"{sum(len(t) for t in ref.values())} turns")
    del ref_backend
    gc.collect()                # its pools leave chip 0 before the fleet's
    holder = []

    def disrupt(wave):
        """During the second wave: one fluid migration, one engine kill."""
        if wave != 1:
            return
        fleet = holder[0]
        home = {a: m.idx for m in fleet.members for a in m.backend.sessions}
        victim = 3
        mover = next(a for a, i in sorted(home.items()) if i != victim)
        target = next(i for i in range(4) if i not in (home[mover], victim))
        deadline = time.monotonic() + 60
        while not fleet.session_busy(mover) and time.monotonic() < deadline:
            time.sleep(0.01)
        mig = fleet.migrate(mover, target, fluid=True)
        fleet.kill_engine(victim)
        log(f"placement {home}; migrate {mover} {home[mover]}->{target}: "
            f"{mig}; kill engine{victim}")

    with tempfile.TemporaryDirectory() as journal:
        outs, fleet = run(["--fleet", "4", "--journal-dir", journal],
                          on_wave=disrupt, fleet_out=holder)
        fs = fleet.fleet_stats()
        devices = [str(m.backend.engine.device) for m in fleet.members]
    log(f"fleet devices {devices}; migrations fluid "
        f"{fs['migrations_fluid']} sudden {fs['migrations_sudden']}, "
        f"engines lost {fs['engines_lost']}, sessions failed over "
        f"{fs['sessions_failed_over']}")
    equal = outs == ref
    log(f"fleet tokens bitwise equal to the single engine: {equal}")
    if not equal:
        for a in ref:
            for i, (got, want) in enumerate(zip(outs[a], ref[a])):
                if got != want:
                    first = next((j for j, (x, y) in enumerate(zip(got, want))
                                  if x != y), min(len(got), len(want)))
                    log(f"  {a} turn {i}: first differs at token {first}: "
                        f"fleet {got} vs single {want}")
        problems.append("fleet tokens differ from the single engine")
    if len(set(devices)) != 4:
        problems.append(f"fleet members share chips: {devices}")
    if fs["migrations_fluid"] < 1 or fs["engines_lost"] != 1:
        problems.append("the fleet phase missed its migration or its kill")


def tp_phase(seed: int, problems: list):
    """(b) tp=4 vs tp=1 on starcoder2-7b, f32, exact greedy tokens."""
    import jax

    from benchmarks.sched_live import parity_tokens
    from repro.configs import get_config
    from repro.launch.mesh import make_tp_mesh
    from repro.launch.serve import init_serving_params

    full = get_config(TP_ARCH)
    cfg = full.replace(remat=False, n_layers=TP_LAYERS,
                       compute_dtype="float32")
    log(f"tp phase: {TP_ARCH} at published widths (d_model {cfg.d_model}, "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads}, d_ff {cfg.d_ff}), depth cut "
        f"{TP_LAYERS} of {full.n_layers} layers, f32, highest precision")
    cfg, params = init_serving_params(cfg, seed)
    ref = parity_tokens(cfg, params, None)
    host = jax.device_get(params)
    del params
    toks = parity_tokens(cfg, host, make_tp_mesh(4))
    log(f"tp=1 tokens {ref}")
    log(f"tp=4 tokens {toks}; exact: {toks == ref}")
    if toks != ref:
        problems.append("tp=4 tokens differ from tp=1")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip fleet and tp=4 phases")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("[chip_smoke] src/repro not found beside this script: run it "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"platform {dev.platform}, device_kind {dev.device_kind}, "
        f"devices {len(devices)}")
    if dev.platform != "tpu":
        print(f"[chip_smoke] no TPU: JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"[chip_smoke] needs {need} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import use_compile_cache
    log(f"compile cache {use_compile_cache()}")
    if args.four_chips:
        # set globally, not in a context: precision contexts are
        # thread-local, and the dispatcher thread traces megasteps too
        jax.config.update("jax_default_matmul_precision", "highest")
    problems: list = []
    phases = [fleet_phase, tp_phase] if args.four_chips else [one_chip]
    for phase in phases:
        # a phase that raises is a failure, but the next phase still runs
        try:
            phase(args.seed, problems)
        except Exception as e:  # noqa: BLE001 — reported, exit is non-zero
            traceback.print_exc()
            problems.append(f"{phase.__name__} raised {e!r}")
        gc.collect()            # free the phase's params and pools
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices[:need]]
        log(f"after {phase.__name__}: bytes_in_use per chip {in_use}")
    if problems:
        for p in problems:
            print(f"[chip_smoke] FAIL: {p}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
