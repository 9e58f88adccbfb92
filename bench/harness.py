"""One cell of the benchmark: set-up, the measured window, the check.

The served path is the normal one: ``repro.launch.serve.build_backend``
(paged megastep engine, every bucket compiled) behind ``AgentRM`` and its
fused MLFQ dispatcher; a cell of N chips runs N one-chip engines behind
the fleet router (``--fleet N``, member ``i`` on chip ``i``). The clients
are one benchmark thread that calls ``AgentRM.submit``; between the
dispatcher and the engines' backend sits ``ClientSide``, a thin wrapper
that timestamps ``begin_turn``, every ``step`` and ``collect``, sees each
output token in the step at which it reaches the host, times each
engine's own step, and sets each turn's output length from the traffic
plan through the backends' ``max_new_tokens`` just before it begins.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import importlib.util
import json
import os
import random
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import flops, model, reference, stats, trace, traffic
from repro.core.middleware import SteppableBackend

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class HarnessError(RuntimeError):
    """The run cannot be measured: no chip, a missing file, a bad spec."""


# --------------------------------------------------------------- the cell
@dataclasses.dataclass
class Cell:
    name: str
    config: Dict
    mix: Dict
    chips: int
    end_to_end: List[Dict]
    per_layer: List[Dict]
    roots: tuple
    arch: Any = None              # the configuration's archs/<name>.py


def load_cell(workload: str, spec: Optional[Dict] = None,
              roots=(BENCH,)) -> Cell:
    """A ``workloads`` entry with its configuration, its traffic mix and
    the metrics it reports, each found by name."""
    if spec is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise HarnessError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]

    def listed(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in spec["end_to_end"] if listed(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (workload in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    config_path = model.find("configs", w["config"], roots)
    config = model.load("configs", w["config"], roots)
    return Cell(workload, config,
                model.load("traffic", w["traffic"], roots), int(w["chips"]),
                e2e, per_layer, tuple(roots),
                config_arch(config, config_path, tuple(roots)))


def _module(kind: str, name: str, roots):
    """``<kind>/<name>.py``, found by name and loaded as a module."""
    path = model.find(kind, name, roots, ext=".py")
    tag = "".join(c if c.isalnum() else "_" for c in name)
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{tag}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, roots) -> Callable:
    """``read(ctx)`` of ``metrics/<name>.py``, found by the metric's name."""
    return _module("metrics", name, roots).read


# What an architecture module defines: ``dims(config)`` the normalised
# sizes (with ``dtype`` and ``vocab_size``); ``param_shapes(d)`` the served
# parameter tree as shape tuples; ``program_config(config, d)`` the served
# program's ModelConfig; ``cache_shapes(d, cache_len)`` the reference's
# cache as a tree of shape tuples; ``forward_chunk(d, control, params,
# cache, toks, nxt, start)`` one block of the reference (with ``control``
# its lower-precision control), returning (cache, max logit, logit of
# ``nxt``, argmax); ``paged_attn(d, rows)`` the paged-attention kernel's
# (FLOPs, bytes) and ``model_flops(d, rows)`` the step's model FLOPs.
ARCH_API = ("dims", "param_shapes", "program_config", "cache_shapes",
            "forward_chunk", "paged_attn", "model_flops")


@functools.lru_cache(maxsize=None)
def arch_module(name: str, roots=(BENCH,)):
    """``archs/<name>.py``, found by name; loaded once per process, so the
    reference's compiled blocks are shared by every cell that names it."""
    mod = _module("archs", name, roots)
    missing = [f for f in ARCH_API if not callable(getattr(mod, f, None))]
    if missing:
        raise HarnessError(f"{mod.__file__} does not define {missing}")
    return mod


def config_arch(config: Dict, path: str, roots=(BENCH,)):
    """The architecture module that the configuration file names."""
    if "arch" not in config:
        raise HarnessError(f"{path}: no \"arch\" key naming its "
                           "architecture module (archs/<name>.py)")
    try:
        return arch_module(config["arch"], tuple(roots))
    except FileNotFoundError as e:
        raise HarnessError(f"{path}: arch {config['arch']!r}: {e}") from None


def peaks_for(kind: str, path: str = os.path.join(BENCH, "peaks.json")):
    with open(path) as f:
        table = json.load(f)
    if kind not in table:
        raise HarnessError(f"device kind {kind!r} is not in the peak table "
                           f"{path}: add its published peaks first")
    return table[kind]


# ---------------------------------------------------- the program's side
def serve_args(config: Dict, engines: int = 1):
    from repro.launch.serve import build_parser
    s = config["serving"]
    return build_parser().parse_args([
        "--arch", config["program_arch"], "--paged",
        "--token-budget", str(s["token_budget"]),
        "--max-batch", str(s["max_batch"]), "--max-len", str(s["max_len"]),
        "--num-blocks", str(s["num_blocks"]),
        "--block-size", str(s["block_size"]), "--fleet", str(engines)])


def member_backends(backend) -> List:
    """The one-engine backends behind ``backend``: a fleet's members in
    order, or the backend itself."""
    members = getattr(backend, "members", None)
    return [m.backend for m in members] if members else [backend]


def byte_ids(text: str, vocab: int) -> np.ndarray:
    """The served path's tokens for ``text``: one per byte."""
    b = np.frombuffer(text.encode("ascii"), np.uint8).astype(np.int32)
    return b % (vocab - 2) + 1


class Recorder:
    """The event log, and the profiler window of a traced run."""

    def __init__(self, trace_dir: Optional[str]):
        self.turns: List[Dict] = []
        self.steps: List[Dict] = []
        self.trace_dir = trace_dir
        self.w0 = self.w1 = float("inf")
        self.tracing = False
        self.traced = None            # host-clock window of the trace
        self._ann = None

    def annotate(self, name: str):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def maybe_trace(self, now: float):
        """Start the profiler at the first step of the window and stop it
        at the first step after it: at a step boundary, so the traced steps
        are whole."""
        import jax
        if self.trace_dir is None:
            return
        if not self.tracing and self.traced is None and now >= self.w0:
            jax.profiler.start_trace(self.trace_dir)
            self.tracing = True
            self._ann = jax.profiler.TraceAnnotation(trace.WINDOW)
            self._ann.__enter__()
            self.traced = [time.perf_counter(), None]
        elif self.tracing and now >= self.w1:
            self.stop_trace()

    def stop_trace(self):
        import jax
        if self.tracing:
            self.traced[1] = time.perf_counter()
            self._ann.__exit__(None, None, None)
            self.tracing = False
            jax.profiler.stop_trace()


class ClientSide(SteppableBackend):
    """The wrapper between the dispatcher and the engines' backend. What
    it does not wrap it hands on to the backend, so the dispatcher sees
    the backend's own surface (a fleet's members and hooks among it).
    Each engine's backend gets its ``step`` timed, so in a fleet every
    step record holds one member's own step, not the fleet's."""

    def __init__(self, inner, rec: Recorder):
        self.inner = inner
        self.parts = member_backends(inner)
        self.fleet = inner if len(self.parts) > 1 else None
        self.rec = rec
        self.pending: Dict[str, Dict] = {}   # agent -> turn record
        self.live: Dict[int, Dict] = {}      # rid -> turn record
        for i, b in enumerate(self.parts):
            b.step = functools.partial(self._engine_step, i, b, b.step)

    def __getattr__(self, name):
        if name == "inner" or name.startswith("__"):
            raise AttributeError(name)
        return getattr(self.inner, name)

    def _engine_step(self, i, backend, step):
        """One engine's step, recorded: its own interval, dispatch width,
        real tokens, rows ``(cache_len, q)`` and pool bytes."""
        eng = backend.engine
        disp0, real0 = eng.tokens_dispatched, eng.tokens_real
        t0 = time.perf_counter()
        rep = step()
        t1 = time.perf_counter()
        width = (eng.tokens_dispatched - disp0) // eng.max_batch
        if width:
            rows = []
            for rid, T in eng.last_serviced.items():
                req = eng.reqs.get(rid)
                if req is not None and req.table is not None:
                    rows.append((req.num_tokens - T, T))
            s = {"t0": t0, "t1": t1, "C": width,
                 "real": eng.tokens_real - real0, "rows": rows,
                 "kv_used": eng.cache.bytes_in_use,
                 "kv_total": eng.cache.bytes_total}
            if self.fleet is not None:
                s["member"] = i
            self.rec.steps.append(s)
        return rep

    def _request(self, rid, agent):
        """The engine request behind ``rid``. A fleet's rids are its own,
        so there the member that holds the agent's session is asked."""
        if self.fleet is None:
            return self.parts[0].engine.reqs.get(rid)
        for b in self.parts:
            r = b.sessions.get(agent)
            if r is not None:
                return b.engine.reqs.get(r)
        return None

    def begin_turn(self, agent_id, context, prompt):
        t = self.pending.pop(agent_id)
        if t.get("begin") is None:
            t["begin"] = time.perf_counter()
        for b in self.parts:
            b.max_new_tokens = t["n_out"]
        with self.rec.annotate("bench.begin_turn"):
            rid = self.inner.begin_turn(agent_id, context, prompt)
        self.live[rid] = t
        return rid

    def step(self):
        self.rec.maybe_trace(time.perf_counter())
        with self.rec.annotate("bench.step"):
            rep = self.inner.step()
        t1 = time.perf_counter()
        for rid in set(rep.serviced) | set(rep.finished):
            t = self.live.get(rid)
            req = None if t is None else self._request(rid, t["agent"])
            if req is None:
                continue
            new = req.out_tokens[len(t["out_ids"]):]
            t["out_ids"] += [int(x) for x in new]
            t["tok_t"] += [t1] * len(new)
        return rep

    def rebalance_for_admission(self, agent_id, prompt):
        return self.inner.rebalance_for_admission(agent_id, prompt)

    def placement(self) -> Dict[str, int]:
        """agent -> the fleet member that holds its session."""
        return {a: m.idx for m in self.fleet.members if m.alive
                for a in m.backend.sessions}

    def collect(self, rid):
        with self.rec.annotate("bench.collect"):
            out = self.inner.collect(rid)
        t = self.live.get(rid)
        if t is not None:
            t["done_t"] = time.perf_counter()
        return out

    def park_turn(self, rid):
        t = self.live.get(rid)
        now = time.perf_counter()
        if t is not None:
            t["parks"] = t.get("parks", 0) + 1
            if self.rec.w0 <= now <= self.rec.w1:
                t["parks_in_window"] = t.get("parks_in_window", 0) + 1
        with self.rec.annotate("bench.park"):
            self.inner.park_turn(rid)

    def resume_turn(self, rid):
        with self.rec.annotate("bench.resume"):
            self.inner.resume_turn(rid)

    def abort_turn(self, rid):
        self.inner.abort_turn(rid)

    def session_busy(self, agent_id):
        return self.inner.session_busy(agent_id)

    def can_admit(self, agent_id, prompt):
        return self.inner.can_admit(agent_id, prompt)

    def victim_parkable(self, rid):
        return self.inner.victim_parkable(rid)


# ---------------------------------------------------------- the clients
class Clients:
    """One thread drives every session slot, closed loop: a slot sends its
    next turn when the reply to the last one has arrived and its think
    time has passed."""

    def __init__(self, rm, wrap, cell: Cell, seed: int, vocab: int):
        self.rm, self.wrap, self.mix, self.vocab = rm, wrap, cell.mix, vocab
        self.slots = traffic.permuted(traffic.plan_slots(cell.mix), seed)
        self.rng = random.Random(f"{seed}:text")
        self.systems = traffic.system_prompts(self.rng, cell.mix)
        self.sessions: Dict[str, List[Dict]] = {}
        self.state = [{"idx": 0, "gen": 0, "len": 0, "agent": None,
                       "handle": None, "turn": None, "due": None}
                      for _ in self.slots]
        self.lateness: List[float] = []

    def _send(self, i: int, n_in: int, n_out: int, due: float,
              fresh: bool):
        st = self.state[i]
        if fresh:
            st["agent"] = f"slot{i}-session{st['gen']}"
            st["gen"] += 1
            st["len"] = 0
            self.sessions[st["agent"]] = []
        body = traffic.text(self.rng, n_in)
        system = self.systems[traffic.tenant_of(i, self.mix)]
        prompt = system + body if fresh else body
        t = {"agent": st["agent"], "slot": i, "n_in": len(prompt),
             "n_out": n_out, "due": due, "tok_t": [], "out_ids": [],
             "prompt_ids": byte_ids(prompt, self.vocab), "parks": 0}
        self.wrap.pending[st["agent"]] = t
        t["submit"] = time.perf_counter()
        self.lateness.append(t["submit"] - due)
        st["handle"] = self.rm.submit(st["agent"], prompt)
        st["turn"] = t
        st["len"] += len(prompt) + n_out
        self.sessions[st["agent"]].append(t)
        self.wrap.rec.turns.append(t)

    def _poll(self, i: int) -> bool:
        """True once slot ``i``'s turn has resolved (result or error)."""
        st = self.state[i]
        t = st["turn"]
        try:
            out = st["handle"].result(timeout=0)
        except TimeoutError:
            return False
        except Exception as e:  # noqa: BLE001 — a failed turn, recorded
            t["failed"] = f"{type(e).__name__}: {e}"
        else:
            t["result_ids"] = [int(x) for x in out.split(":", 1)[1].split(",")
                               if x]
        t["reply_t"] = time.perf_counter()
        st["handle"] = None
        return True

    def setup(self, timeout: float = 900.0):
        """Every session's first prefill: the system prompt plus its
        history, one token out. One slot of each tenant goes first, so
        each system prompt is computed once and the others adopt it from
        the prefix index."""
        hist = [s.history for s in self.slots]
        n = min(traffic.tenants(self.mix), len(self.slots))
        order = [list(range(n)), list(range(n, len(self.slots)))]
        deadline = time.perf_counter() + timeout
        for group in order:
            for i in group:
                self._send(i, hist[i], 1, time.perf_counter(), fresh=True)
            while not all(self._poll(i) for i in group
                          if self.state[i]["handle"] is not None):
                if time.perf_counter() > deadline:
                    raise HarnessError("set-up turns did not finish")
                time.sleep(0.005)

    def run(self, w0: float, w1: float):
        """Closed loop until ``w1``; the first turns are due at ``w0`` plus
        their think time."""
        for i, st in enumerate(self.state):
            st["due"] = w0 + self.slots[i].turns[0].think_s
        while True:
            now = time.perf_counter()
            if now >= w1:
                return
            for i, st in enumerate(self.state):
                if st["handle"] is not None:
                    if self._poll(i):
                        turns = self.slots[i].turns
                        st["idx"] += 1
                        st["due"] = (time.perf_counter()
                                     + turns[st["idx"] % len(turns)].think_s)
                    continue
                if st["due"] <= now:
                    turn = self.slots[i].turns[st["idx"] % len(self.slots[i]
                                                               .turns)]
                    fresh = (st["len"] + turn.n_in + turn.n_out
                             > self.mix["reset_after_tokens"])
                    self._send(i, turn.n_in, turn.n_out, st["due"], fresh)
            nxt = min([st["due"] for st in self.state
                       if st["handle"] is None] + [w1])
            time.sleep(min(max(nxt - time.perf_counter(), 0.0), 0.005))

    def placement(self) -> List[tuple]:
        """(tenant, member) of every slot's current session that the
        engines hold."""
        where = self.wrap.placement()
        return [(traffic.tenant_of(i, self.mix), where[st["agent"]])
                for i, st in enumerate(self.state) if st["agent"] in where]


# ------------------------------------------------------------- the check
def served_sequences(sessions: Dict[str, List[Dict]], seed: int,
                     max_tokens: int):
    """Token streams to compare: a seed-drawn sample of sessions, the one
    with the longest served turn first, up to ``max_tokens`` in all. A
    session counts up to its last turn that served a token."""
    streams = []
    for agent, turns in sessions.items():
        used = []
        for t in turns:
            if not t["out_ids"]:
                break
            used.append(t)
        if used:
            toks, served = reference.session_sequences(used)
            longest = max(len(t["out_ids"]) for t in used)
            streams.append((agent, toks, served, longest))
    if not streams:
        return []
    random.Random(f"{seed}:sample").shuffle(streams)
    longest = max(range(len(streams)), key=lambda i: streams[i][3])
    streams.insert(0, streams.pop(longest))
    out, total = [], 0
    for agent, toks, served, _ in streams:
        if out and total + len(toks) > max_tokens:
            continue
        out.append((toks, served))
        total += len(toks)
    return out


def turn_faults(turns: List[Dict]) -> Dict[str, int]:
    """Turns that failed, finished short of their planned length, or whose
    reply differs from the tokens the engine produced."""
    failed = sum(1 for t in turns if t.get("failed"))
    short = altered = 0
    for t in turns:
        if "result_ids" in t:
            short += len(t["result_ids"]) != t["n_out"]
            altered += t["result_ids"] != t["out_ids"]
    return {"turns_failed": failed, "turns_short": short,
            "replies_altered": altered}


# ------------------------------------------------------------- the run
def run_cell(cell: Cell, seed: int, seconds: float, trace_on: bool, *,
             t_start: float, require_tpu: bool = True,
             break_path: Optional[Callable] = None,
             control: bool = False) -> Dict:
    """Run one cell and return its result line. With ``control`` the float8
    control takes the served program's place in the comparison, and the
    result line also carries the program's own ``program_logit_gap``."""
    import jax

    t_entry = time.perf_counter()
    devices = jax.devices()
    t_devices = time.perf_counter()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise HarnessError(f"no TPU: JAX found {dev.platform}")
    if len(devices) < cell.chips:
        raise HarnessError(f"the cell needs {cell.chips} chips, JAX found "
                           f"{len(devices)}")
    peaks = peaks_for(dev.device_kind) if require_tpu else None
    limit = cell.config["check"]["max_logit_gap"]
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR", CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles: List[float] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *a, **k: compiles.append(time.perf_counter())
        if name == "/jax/core/compile/jaxpr_to_mlir_module_duration" else None)

    from repro.core import AgentRM, AgentRMConfig
    from repro.launch.serve import build_backend
    from repro.models import abstract_params
    from repro.obs import Observability

    phases = {"imports_s": t_entry - t_start,
              "devices_s": t_devices - t_entry,
              "start_s": time.perf_counter() - t_start}
    config, arch = cell.config, cell.arch
    d = arch.dims(config)
    cfg = arch.program_config(config, d)
    t = time.perf_counter()
    params = jax.block_until_ready(
        model.make_params(arch.param_shapes(d), d["dtype"], seed))
    phases["weights_s"] = time.perf_counter() - t
    want = jax.tree.map(lambda s: (s.shape, s.dtype), abstract_params(cfg))
    have = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    if want != have:
        raise HarnessError("the benchmark's weights do not match the "
                           "program's parameter tree")
    t = time.perf_counter()
    _, backend = build_backend(cfg, params,
                               serve_args(config, cell.chips),
                               obs=Observability())
    phases["compile_s"] = time.perf_counter() - t
    parts = member_backends(backend)
    if break_path is not None:
        for b in parts:
            break_path(b.engine)
    for b in parts:
        b.prompt_tokens = config["serving"]["max_len"]
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace_on else None
    rec = Recorder(trace_dir)
    wrap = ClientSide(backend, rec)
    rm = AgentRM(wrap, AgentRMConfig(
        lanes=config["serving"]["max_batch"] * cell.chips,
        detect_after_s=120.0))
    clients = Clients(rm, wrap, cell, seed, d["vocab_size"])
    try:
        t = time.perf_counter()
        clients.setup()
        phases["history_s"] = time.perf_counter() - t
        setup_turns = len(rec.turns)
        w0 = time.perf_counter()
        w1 = w0 + seconds
        rec.w0, rec.w1 = w0, w1
        clients.run(w0, w1)
        placement = clients.placement() if wrap.fleet is not None else []
    finally:
        rm.shutdown()
        for th in (rm._dispatcher, rm._reaper):
            th.join(timeout=600)
        rec.stop_trace()
    setup_s = w0 - t_start
    in_window = sum(1 for c in compiles if w0 <= c <= w1)
    mems = [x.memory_stats() or {} for x in devices[:cell.chips]]
    peak = max((m["peak_bytes_in_use"] for m in mems
                if "peak_bytes_in_use" in m), default=None)
    engines = [b.engine for b in parts]
    moves = ({k: v for k, v in backend.fleet_stats().items()
              if "migrations" in k} if cell.chips > 1 else None)
    st = [e.step_stats() for e in engines]
    kv = [e.kv_stats() for e in engines]
    bucket_compile = (dict(engines[0].compile_seconds) if len(engines) == 1
                      else {f"{e.name}/{k}": v for e in engines
                            for k, v in e.compile_seconds.items()})
    del rm, wrap, backend, parts, engines, clients.rm, clients.wrap
    gc.collect()

    turns = rec.turns[setup_turns:]
    e2e = stats.end_to_end(turns, w0, w1)
    faults = turn_faults(rec.turns)
    max_ref = config["serving"]["max_len"] * 12
    seqs = served_sequences(clients.sessions, seed, max_ref)
    t = time.perf_counter()
    chk = reference.compare(arch, d, params, seqs,
                            config["serving"]["max_len"], control=control)
    ref_s = time.perf_counter() - t

    compared = {
        "logit_gap": {"value": chk["max_logit_gap"], "limit": limit,
                      "rule": "<="},
        "served_checked": {"value": chk["served_checked"], "limit": 32,
                           "rule": ">="},
        **{k: {"value": v, "limit": 0, "rule": "<="}
           for k, v in faults.items()},
        "compiles_in_window": {"value": in_window, "limit": 0, "rule": "<="},
    }
    correct = all((c["value"] <= c["limit"]) if c["rule"] == "<=" else
                  (c["value"] >= c["limit"]) for c in compared.values())

    def each(xs, key):
        """One member's value, or the list of every member's."""
        vals = [x.get(key) for x in xs]
        return vals[0] if len(vals) == 1 else vals

    report = {
        "setup": {**phases, "setup_s": setup_s},
        "samples": e2e["samples"], "window_s": w1 - w0,
        "ttft_p95_s": e2e["ttft_p95_s"],
        "setup_turns": setup_turns,
        "padded_token_fraction": each(st, "padded_token_fraction"),
        "trace_buckets": each(st, "trace_buckets"),
        "generator_lateness_s": {
            "p50": stats.quantile(clients.lateness, 0.5),
            "max": max(clients.lateness) if clients.lateness else None},
        "peak_bytes_in_use": each(mems, "peak_bytes_in_use"),
        "bytes_limit": mems[0].get("bytes_limit"),
        "compiles_in_window": in_window,
        "kv_blocks_in_use": each(kv, "blocks_in_use"),
        "kv_blocks_total": each(kv, "blocks_total"),
        "prefix_hits": each(kv, "prefix_hits"),
        "swap_bytes_out": each(kv, "swap_bytes_out"),
        "reference_s": ref_s, "argmax_agreement": chk["argmax_agreement"],
        "sessions_checked": len(seqs),
    }
    if cell.chips > 1:
        report["sessions_by_member"] = [
            sum(1 for _, m in placement if m == i)
            for i in range(cell.chips)]
        report["fleet_migrations"] = moves
    log("report " + json.dumps(report))
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    metrics: Dict[str, Dict] = {}
    breakdown = None
    if trace_on:
        events = trace.extract(trace_dir, cell.chips)
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo_hi = trace.window(events)
        lo, hi = lo_hi if lo_hi else (0.0, 0.0)
        device["busy_s"] = trace.busy_ns(events, lo, hi) * 1e-9 / cell.chips
        device["window_s"] = (hi - lo) * 1e-9
        breakdown = {"device_ops": trace.top_ops(events, lo, hi),
                     "idle_gaps": trace.idle_gaps(events, lo, hi)}
        h0, h1 = rec.traced or (w0, w1)
        ctx = SimpleNamespace(
            dims=d, peaks=peaks, flops=flops.counts(arch), stats=stats,
            trace=trace, chips=cell.chips, members=cell.chips,
            events=events, trace_window=(lo, hi), window=(h0, h1),
            steps=[s for s in rec.steps if s["t0"] >= h0 and s["t1"] <= h1],
            turns=turns, compile_seconds=bucket_compile, phases=phases,
            placement=placement)
        if peaks is not None:
            bound = [flops.least_time(*arch.paged_attn(d, s["rows"]),
                                      peaks)[1] for s in ctx.steps]
            log(f"traced steps {len(ctx.steps)}; paged-attention roofline "
                f"bound by flops in {bound.count('flops')}, by bytes in "
                f"{bound.count('bytes')}")
        for m in cell.per_layer:
            v = metric_reader(m["name"], cell.roots)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = {**e2e, "setup_s": setup_s}
        for m in cell.end_to_end:
            v = values.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {"correct": bool(correct), "attempted": len(turns),
           "failed": faults["turns_failed"], "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if control:
        out["program_logit_gap"] = chk["program_max_logit_gap"]
    out["compared"] = compared
    return out
