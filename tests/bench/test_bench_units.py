"""The benchmark's yardstick on the CPU: the traffic generator, the
end-to-end arithmetic, the work counts, the trace reduction, the peak
table, the architecture modules and how files are found by name. Nothing
here needs a chip."""
import glob
import hashlib
import json
import math
import os
import re
import shutil
import sys
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from bench import flops, harness, model, reference  # noqa: E402
from bench import stats, trace, traffic  # noqa: E402

DATA = os.path.join(REPO, "tests", "bench", "data")
GQA = harness.arch_module("gqa")
MIXES = ["agent-tools", "short-chat"]


# ----------------------------------------------------------- traffic
@pytest.mark.parametrize("mix", MIXES)
def test_traffic_plan_is_seeded_and_within_its_parameters(mix):
    m = model.load("traffic", mix)
    a, b = traffic.plan_slots(m), traffic.plan_slots(m)
    assert a == b and len(a) == m["sessions"]
    limit, sys_tok = m["reset_after_tokens"], m["system_prompt_tokens"]
    for slot in a:
        h = m.get("history_tokens")
        if h:
            assert h["lo"] <= slot.history <= h["hi"]
        for t in slot.turns:
            out = m["output_tokens"]
            assert out["lo"] <= t.n_out <= out["hi"]
            inp = m["input_tokens"]
            assert 1 <= t.n_in <= inp["cap"]
            assert t.n_in >= min(inp["min"], limit - sys_tok - t.n_out - 1)
            assert sys_tok + t.n_in + t.n_out < limit
            assert t.think_s > 0


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_gets_the_same_work_in_another_order(mix):
    m = model.load("traffic", mix)
    slots = traffic.plan_slots(m)
    key = sorted((s.history, tuple((t.n_in, t.n_out, t.think_s)
                                   for t in s.turns)) for s in slots)
    for seed in (1, 2**31 + 7):
        p = traffic.permuted(slots, seed)
        assert sorted((s.history, tuple((t.n_in, t.n_out, t.think_s)
                                        for t in s.turns))
                      for s in p) == key
        assert p == traffic.permuted(slots, seed)


def test_every_session_gets_identical_system_prompt_bytes():
    cell = harness.Cell("x", {}, model.load("traffic", "agent-tools"), 1,
                        [], [], (harness.BENCH,))
    sent = []

    class FakeRM:
        def submit(self, agent, prompt):
            sent.append(prompt)
            return None

    class FakeWrap:
        pending = {}
        rec = harness.Recorder(None)

    c = harness.Clients(FakeRM(), FakeWrap(), cell, seed=5, vocab=65024)
    for i in range(len(c.slots)):
        c._send(i, c.slots[i].history, 1, 0.0, fresh=True)
    n = cell.mix["system_prompt_tokens"]
    assert len({p[:n] for p in sent}) == 1
    assert all(len(p) == n + s.history for p, s in zip(sent, c.slots))
    c2 = harness.Clients(FakeRM(), FakeWrap(), cell, seed=5, vocab=65024)
    assert c2.systems == c.systems and len(c.systems) == 1
    assert harness.Clients(FakeRM(), FakeWrap(), cell, seed=6,
                           vocab=65024).systems != c.systems


def _sent(mix_name, seed):
    """Every prompt the clients send for ``mix_name`` and ``seed``: each
    session's first prefill, then each one's first turn."""
    cell = harness.Cell("x", {}, model.load("traffic", mix_name), 1,
                        [], [], (harness.BENCH,))
    sent = []

    class FakeRM:
        def submit(self, agent, prompt):
            sent.append((agent, prompt))

    class FakeWrap:
        pending = {}
        rec = harness.Recorder(None)

    c = harness.Clients(FakeRM(), FakeWrap(), cell, seed=seed, vocab=65024)
    for i in range(len(c.slots)):
        c._send(i, c.slots[i].history, 1, 0.0, fresh=True)
    for i in range(len(c.slots)):
        t = c.slots[i].turns[0]
        c._send(i, t.n_in, t.n_out, 0.0, fresh=False)
    return c, sent


# digests of each plan and of every prompt sent, recorded before mixes
# could name tenants
TRAFFIC_GOLDEN = {
    ("agent-tools", 5):
        "2cf7e7f18649492e6354676c80b38712c22766b30c7e07070c3715a51977494c",
    ("agent-tools", 2**31 + 12345):
        "23ab794135e7908ac8680e673ad85802811f3091b23f9111401c4bb5d78756b9",
    ("short-chat", 5):
        "b2d083a8b463b4360b8726c2883dbad56de5149c8b19ffd5b921a4c5b1179385",
    ("short-chat", 2**31 + 12345):
        "fc6776a45012db212d25956dfb6a83a60233a5b17db8b574da4d952e65690375",
}


@pytest.mark.parametrize("mix,seed", sorted(TRAFFIC_GOLDEN))
def test_a_mix_without_tenants_sends_the_recorded_traffic(mix, seed):
    c, sent = _sent(mix, seed)
    h = hashlib.sha256()
    h.update(json.dumps([[s.history, [[t.n_in, t.n_out, repr(t.think_s)]
                                      for t in s.turns]]
                         for s in c.slots]).encode())
    h.update(json.dumps(sent).encode())
    assert h.hexdigest() == TRAFFIC_GOLDEN[mix, seed]


def test_tenants_have_distinct_prompts_and_take_slots_round_robin():
    c, sent = _sent("fleet4-tenants", 2**31 + 99)
    m = c.mix
    n, k = m["system_prompt_tokens"], m["tenants"]
    assert len(c.systems) == k == 4
    assert len(set(c.systems)) == k
    assert all(len(p) == n for p in c.systems)
    first = sent[:m["sessions"]]
    for i, (agent, prompt) in enumerate(first):
        assert agent.startswith(f"slot{i}-")
        assert prompt[:n] == c.systems[i % k]
        assert len(prompt) == n + c.slots[i].history
    # the first tenant's prompt is the one prompt of a mix without tenants
    shared, _ = _sent("fleet4-shared", 2**31 + 99)
    assert shared.systems == c.systems[:1]
    assert [s.history for s in c.slots] == [s.history for s in shared.slots]


def test_set_up_prefills_one_slot_of_each_tenant_first():
    """The others are sent once those have finished, so each tenant's
    prompt is computed once and adopted from the prefix index."""
    events = []

    class Done:
        def __init__(self, agent):
            self.agent = agent

        def result(self, timeout=None):
            events.append(("done", self.agent))
            return "tok:1"

    class FakeRM:
        def submit(self, agent, prompt):
            events.append(("sent", agent))
            return Done(agent)

    class FakeWrap:
        pending = {}
        rec = harness.Recorder(None)

    cell = harness.Cell("x", {}, model.load("traffic", "fleet4-tenants"), 4,
                        [], [], (harness.BENCH,))
    c = harness.Clients(FakeRM(), FakeWrap(), cell, seed=3, vocab=65024)
    c.setup()
    sent = [a for e, a in events if e == "sent"]
    first_rest = events.index(("sent", sent[4]))
    assert sent[:4] == [f"slot{i}-session0" for i in range(4)]
    assert {a for e, a in events[:first_rest] if e == "done"} == set(sent[:4])
    assert len(sent) == 64


def test_the_fleet_mixes_send_the_same_work():
    a = model.load("traffic", "fleet4-tenants")
    b = model.load("traffic", "fleet4-shared")
    assert {k: v for k, v in a.items() if k not in ("about", "assumed",
                                                    "tenants")} == \
        {k: v for k, v in b.items() if k not in ("about", "assumed",
                                                 "tenants")}
    assert traffic.plan_slots(a) == traffic.plan_slots(b)
    assert (a["tenants"], b["tenants"]) == (4, 1)


def test_byte_tokens_are_one_per_byte():
    ids = harness.byte_ids("ab~", 65024)
    assert list(ids) == [ord("a") + 1, ord("b") + 1, ord("~") + 1]


# ---------------------------------------------------- end-to-end math
def _turn(due, toks, n_out, submit=None, begin=None, failed=None):
    return {"due": due, "tok_t": toks, "n_out": n_out, "submit": submit,
            "begin": begin, "failed": failed}


def test_end_to_end_arithmetic_with_censoring_and_edges():
    w0, w1 = 10.0, 20.0
    turns = [
        _turn(9.0, [9.5, 10.5, 11.0], 3),          # due before the window
        _turn(12.0, [13.0, 14.0, 18.0], 5),        # decoding at the end
        _turn(19.0, [], 4),                        # no token yet: censored
        _turn(15.0, [21.0], 4),                    # first token after w1
        _turn(20.0, [20.5], 1),                    # due at w1: not counted
    ]
    e = stats.end_to_end(turns, w0, w1)
    assert e["out_tok_s"] == pytest.approx(5 / 10)
    assert sorted(stats.ttft_samples(turns, w0, w1)) == \
        pytest.approx([1.0, 1.0, 5.0])
    assert e["ttft_p50_s"] == pytest.approx(1.0)
    assert e["ttft_p95_s"] == pytest.approx(1.0 + 0.9 * 4.0)
    assert sorted(stats.itl_samples(turns, w0, w1)) == \
        pytest.approx([0.5, 0.5, 1.0, 2.0, 4.0])
    assert e["itl_p95_s"] == pytest.approx(2.0 + 0.8 * 2.0)
    assert e["samples"]["first_tokens"] == 3


def test_tail_ttft_reader_is_the_end_to_end_p95_of_the_traced_window():
    turns = [_turn(12.0, [13.0], 1), _turn(15.0, [21.0], 4),
             _turn(19.0, [], 4), _turn(11.0, [11.2], 1)]
    ctx = SimpleNamespace(window=(10.0, 20.0), turns=turns, stats=stats)
    read = harness.metric_reader("ttft_tail_p95_s", (harness.BENCH,))
    assert read(ctx) == pytest.approx(
        stats.end_to_end(turns, 10.0, 20.0)["ttft_p95_s"])
    assert read(SimpleNamespace(window=(30.0, 40.0), turns=turns,
                                stats=stats)) is None


def test_queue_wait_counts_unbegun_turns_until_the_window_ends():
    turns = [_turn(0, [], 1, submit=11.0, begin=12.5),
             _turn(0, [], 1, submit=18.0, begin=None),
             _turn(0, [], 1, submit=5.0, begin=15.0)]
    assert stats.queue_wait_samples(turns, 10.0, 20.0) == \
        pytest.approx([1.5, 2.0])


def test_a_failed_turn_leaves_no_open_gap():
    t = _turn(11.0, [12.0], 5, failed="EngineError")
    assert stats.itl_samples([t], 10.0, 20.0) == []


# --------------------------------------------------------- work counts
D = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
     "head_dim": 16, "d_ff": 128, "vocab_size": 256, "mlp": "swiglu"}


def test_attention_counts_match_a_hand_computed_case():
    # prefill row: 3 new tokens after 5 cached; causal pairs = 3*8 - 3 = 21
    assert GQA.attn_flops_row(D, 5, 3) == 4 * 4 * 16 * 21
    # K and V of 8 positions for 2 kv heads, bf16, plus q and out of 3 rows
    assert GQA.attn_bytes_row(D, 5, 3) == 2 * 2 * 16 * 8 * 2 + \
        2 * 3 * 4 * 16 * 2
    f, b = GQA.paged_attn(D, [(5, 3), (9, 1)])
    assert f == 2 * (4 * 4 * 16 * 21 + 4 * 4 * 16 * 10)
    assert b == 2 * (GQA.attn_bytes_row(D, 5, 3)
                     + GQA.attn_bytes_row(D, 9, 1))


def test_counts_are_brute_force_pairs():
    for cache_len, q in [(0, 1), (0, 7), (13, 1), (100, 37)]:
        pairs = sum(1 for i in range(q) for j in range(cache_len + q)
                    if j <= cache_len + i)
        assert GQA.attn_flops_row(D, cache_len, q) == \
            4 * D["n_heads"] * D["head_dim"] * pairs


@pytest.mark.parametrize("serving", [
    {"max_len": 8192, "block_size": 16, "max_batch": 8, "token_budget": 1024},
    {"max_len": 2048, "block_size": 64, "max_batch": 2, "token_budget": 64}])
def test_counts_do_not_depend_on_the_kernel_grid(serving):
    """Pages walked and dispatch widths are not inputs: the same live rows
    give the same work whatever the pool's page size or the batch width."""
    rows = [(1500, 1), (0, 200), (37, 1)]
    d = dict(D, **serving)
    assert GQA.paged_attn(d, rows) == GQA.paged_attn(D, rows)
    assert GQA.model_flops(d, rows) == GQA.model_flops(D, rows)


def test_model_flops_count_the_head_once_per_row():
    per_tok = 2 * GQA.matmul_params(D)
    head = 2 * 64 * 256
    assert GQA.model_flops(D, [(0, 4)]) == \
        4 * per_tok + head + 2 * GQA.attn_flops_row(D, 0, 4)
    # chatglm3-6b: 5.6e9 non-embedding matmul weights
    d = GQA.dims(model.load("configs", "chatglm3-6b"))
    assert 5.5e9 < GQA.matmul_params(d) < 5.8e9


def test_least_time_names_its_bound():
    peaks = {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_time(1000, 50, peaks) == (10.0, "flops")
    assert flops.least_time(100, 50, peaks) == (5.0, "bytes")


# ------------------------------------------------------ trace reduction
def _trace():
    with open(os.path.join(DATA, "trace_small.json")) as f:
        return json.load(f)


def _brute_busy(events, lo, hi, step=1000.0):
    n = int((hi - lo) // step)
    busy = 0
    for i in range(n):
        t = lo + (i + 0.5) * step
        busy += any(e[1] <= t < e[1] + e[2] for e in events["device"])
    return busy * step


def test_trace_reduction_on_a_recorded_trace():
    ev = _trace()
    lo, hi = trace.window(ev)
    busy = trace.busy_ns(ev, lo, hi)
    assert busy == pytest.approx(_brute_busy(ev, lo, hi), rel=2e-3)
    assert 0 < busy <= hi - lo
    gaps = trace.idle_gaps(ev, lo, hi, n=10**6)
    assert sum(g for _, g in gaps) * 1e9 == pytest.approx(hi - lo - busy,
                                                          rel=1e-9)
    top = trace.top_ops(ev, lo, hi, n=10**6)
    # innermost ops do not overlap and fill nearly all of the busy time
    assert 0.99 * busy <= sum(s for _, s in top) * 1e9 <= busy
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    from bench.kernels import is_paged_attn
    k = trace.op_ns(ev, lo, hi, is_paged_attn)
    assert 0 < k <= busy
    # recorded values: a decode step of agent-tools, nearly all kernel
    assert busy == pytest.approx(1.002070672e9, rel=1e-9)
    assert k == pytest.approx(0.963288918e9, rel=1e-9)


def _one_chip_ctx(ev, steps, chips=1, members=1):
    lo, hi = trace.window(ev)
    d = GQA.dims(model.load("configs", "chatglm3-6b"))
    return SimpleNamespace(
        dims=d, peaks=harness.peaks_for("TPU v5 lite"),
        flops=flops.counts(GQA), stats=stats, trace=trace, events=ev,
        trace_window=(lo, hi), window=(0.0, 1.0), steps=steps, turns=[],
        compile_seconds={}, phases={}, chips=chips, members=members,
        placement=[])


def _recorded_readers():
    with open(os.path.join(DATA, "one_chip_readers.json")) as f:
        return json.load(f)


DEVICE_READERS = ["device_idle_frac", "paged_attn.busy_frac",
                  "paged_attn_roofline", "step_mfu", "kv_in_use_frac"]


@pytest.mark.parametrize("tagged", [False, True],
                         ids=["untagged", "tagged_chip0"])
def test_one_chip_readers_read_the_recorded_values(tagged):
    """On the recorded one-chip trace every reader returns, to the bit,
    the value it returned before the trace kept several chips; tagging
    each op with chip 0, as ``extract`` now does, changes nothing."""
    g = _recorded_readers()
    ev = _trace()
    if tagged:
        ev = {"device": [e + [0] for e in ev["device"]], "host": ev["host"]}
    steps = [dict(s, rows=[tuple(r) for r in s["rows"]]) for s in g["steps"]]
    ctx = _one_chip_ctx(ev, steps)
    for name in DEVICE_READERS:
        assert harness.metric_reader(name, (harness.BENCH,))(ctx) == \
            g["readers"][name], name
    lo, hi = trace.window(ev)
    assert trace.busy_ns(ev, lo, hi) == g["busy_ns"]
    assert trace.top_ops(ev, lo, hi) == g["top_ops"]
    assert trace.idle_gaps(ev, lo, hi) == g["idle_gaps"]


def _two_chips(ev, shift_ns):
    """The recorded chip as chip 0 and a copy of it, ``shift_ns`` later,
    as chip 1."""
    dev = [e + [0] for e in ev["device"]]
    dev += [[e[0], e[1] + shift_ns, e[2], e[3], 1] for e in ev["device"]]
    return {"device": dev, "host": ev["host"]}


@pytest.mark.parametrize("shift_ns", [0.0, 37e6], ids=["aligned", "shifted"])
def test_two_chip_trace_sums_each_chips_own_busy_and_kernel_time(shift_ns):
    one = _trace()
    two = _two_chips(one, shift_ns)
    lo, hi = trace.window(one)
    moved = {"device": [[e[0], e[1] + shift_ns, e[2], e[3]]
                        for e in one["device"]], "host": one["host"]}
    assert trace.busy_ns(two, lo, hi) == \
        trace.busy_ns(one, lo, hi) + trace.busy_ns(moved, lo, hi)
    assert len(trace.leaves(two)) == 2 * len(trace.leaves(one))
    from bench.kernels import is_paged_attn
    assert trace.op_ns(two, lo, hi, is_paged_attn) == pytest.approx(
        trace.op_ns(one, lo, hi, is_paged_attn)
        + trace.op_ns(moved, lo, hi, is_paged_attn), rel=1e-12)
    gaps = trace.idle_gaps(two, lo, hi, n=10**6)
    assert sum(g for _, g in gaps) * 1e9 == pytest.approx(
        2 * (hi - lo) - trace.busy_ns(two, lo, hi), rel=1e-9)
    assert {g[0].rsplit(" @", 1)[1] for g in gaps} == {"chip0", "chip1"}


def test_two_chip_readers_average_the_chips_and_sum_the_work():
    """Two chips that each ran the recorded step, each engine serving the
    same rows: every share reads as one chip's does."""
    g = _recorded_readers()
    steps = [dict(s, rows=[tuple(r) for r in s["rows"]]) for s in g["steps"]]
    two = _two_chips(_trace(), 0.0)
    ctx = _one_chip_ctx(two, [dict(s, member=m) for m in (0, 1)
                              for s in steps], chips=2, members=2)
    for name in DEVICE_READERS:
        assert harness.metric_reader(name, (harness.BENCH,))(ctx) == \
            pytest.approx(g["readers"][name], rel=1e-12), name
    # read as one chip, the same events would count the busy time twice
    ctx.chips = 1
    idle = harness.metric_reader("device_idle_frac", (harness.BENCH,))(ctx)
    assert idle < 0


def test_fleet_readers():
    tok = harness.metric_reader("fleet.tokens_imbalance", (harness.BENCH,))
    col = harness.metric_reader("fleet.tenant_colocation", (harness.BENCH,))
    steps = [{"member": 0, "real": 30}, {"member": 1, "real": 10},
             {"member": 0, "real": 20}, {"member": 3, "real": 20}]
    ctx = SimpleNamespace(members=4, steps=steps, placement=[
        (0, 0), (0, 0), (0, 1), (1, 2), (1, 2), (2, 3)])
    assert tok(ctx) == 50 / 20            # member 2 served nothing
    assert col(ctx) == (2 + 2 + 1) / 6
    ctx.placement = [(t, t) for t in range(4) for _ in range(16)]
    assert col(ctx) == 1.0
    one = SimpleNamespace(members=1, steps=[{"real": 5}], placement=[(0, 0)])
    assert tok(one) is None and col(one) is None
    assert tok(SimpleNamespace(members=2, steps=[], placement=[])) is None


def test_idle_gaps_are_named_by_the_open_annotation():
    ev = {"device": [["a", 0, 10, ""], ["b", 35, 10, ""], ["c", 60, 40, ""]],
          "host": [[trace.WINDOW, 0, 100], ["bench.step", 0, 45],
                   ["bench.collect", 41, 3]]}
    assert trace.busy_ns(ev, 0, 100) == 60
    gaps = trace.idle_gaps(ev, 0, 100)
    assert [g[0] for g in gaps] == ["bench.step", "host"]
    assert [g[1] for g in gaps] == pytest.approx([25e-9, 15e-9])
    assert trace.top_ops(ev, 0, 100)[0] == ["c", pytest.approx(40e-9)]


# ------------------------------------------------------------ peaks
def test_the_peak_table_refuses_an_unknown_device():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(harness.HarnessError):
        harness.peaks_for("TPU v99 imaginary")


# ------------------------------------------------------- found by name
def _tree_digest(root):
    h = hashlib.sha256()
    for dirpath, dirs, files in sorted(os.walk(root)):
        dirs.sort()
        for f in sorted(files):
            if f.endswith(".pyc"):
                continue
            p = os.path.join(dirpath, f)
            h.update(p.encode())
            with open(p, "rb") as fh:
                h.update(fh.read())
    return h.hexdigest()


def _greedy(arch, d, params, prompt, n):
    """``prompt`` and ``n`` tokens that the reference puts first."""
    toks = list(prompt)
    for _ in range(n):
        arg = reference._run(arch, d, False, params,
                             np.asarray(toks + [0], np.int32), None,
                             reference.CHUNK)[2]
        toks.append(int(arg[-1]))
    return toks


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    before = _tree_digest(harness.BENCH)
    for kind in ("configs", "traffic", "metrics", "archs"):
        (tmp_path / kind).mkdir()
    shutil.copy(GQA.__file__, tmp_path / "archs" / "new-arch.py")
    cfg = dict(model.load("configs", "tiny", roots=(DATA,)), arch="new-arch")
    (tmp_path / "configs" / "new-model.json").write_text(json.dumps(cfg))
    mix = dict(model.load("traffic", "short-chat"), sessions=3)
    (tmp_path / "traffic" / "new-mix.json").write_text(json.dumps(mix))
    (tmp_path / "metrics" / "new_metric.x.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    spec = {"workloads": [{"name": "new-model.new-mix", "config": "new-model",
                           "traffic": "new-mix", "chips": 1, "why": "t"}],
            "end_to_end": [{"name": "out_tok_s", "unit": "tokens/s"}],
            "per_layer": [{"name": "new_metric.x", "unit": "s",
                           "moves": "out_tok_s"},
                          {"name": "step_mfu", "unit": "%",
                           "moves": "out_tok_s", "workloads": ["other"]}]}
    roots = (str(tmp_path), harness.BENCH)
    cell = harness.load_cell("new-model.new-mix", spec, roots=roots)
    assert cell.config == cfg and cell.mix["sessions"] == 3
    assert [m["name"] for m in cell.per_layer] == ["new_metric.x"]
    assert harness.metric_reader("new_metric.x", roots)(None) == 42.0
    assert harness.metric_reader("step_mfu", roots).__module__ \
        .endswith("step_mfu")
    # the new architecture module is the one the cell runs, and the
    # reference scores a served sequence through it
    arch = cell.arch
    assert arch is not GQA
    assert arch.__file__ == str(tmp_path / "archs" / "new-arch.py")
    d = arch.dims(cell.config)
    params = model.make_params(arch.param_shapes(d), d["dtype"], 3)
    toks = _greedy(GQA, d, params, [5, 9, 14, 200, 31, 77, 2, 64], 6)
    served = [False] * 8 + [True] * 6
    out = reference.compare(arch, d, params, [(toks, served)],
                            cell.config["serving"]["max_len"])
    assert out == {"max_logit_gap": 0.0, "served_checked": 6,
                   "argmax_agreement": 1.0}
    toks[10] = (toks[10] + 1) % d["vocab_size"]
    out = reference.compare(arch, d, params, [(toks, served)],
                            cell.config["serving"]["max_len"])
    assert out["max_logit_gap"] > 0 and out["argmax_agreement"] < 1
    assert _tree_digest(harness.BENCH) == before


@pytest.mark.parametrize("chips", [1, 2, 4])
def test_a_cell_runs_one_engine_per_chip(chips):
    spec = {"workloads": [{"name": "tiny.x", "config": "tiny",
                           "traffic": "tiny-fleet", "chips": chips,
                           "why": "t"}],
            "end_to_end": [], "per_layer": []}
    cell = harness.load_cell("tiny.x", spec, roots=(DATA, harness.BENCH))
    args = harness.serve_args(cell.config, cell.chips)
    assert cell.chips == args.fleet == chips


def test_each_fleet_member_records_its_own_step():
    """A fleet steps its members one after another: every step record
    holds one member's own interval and work, and the tokens reach each
    turn through the member that holds its session."""
    class Engine:
        max_batch = 2

        def __init__(self, work_s):
            self.work_s, self.tokens_dispatched, self.tokens_real = \
                work_s, 0, 0
            self.reqs = {7: SimpleNamespace(out_tokens=[], table=[0],
                                            num_tokens=10)}
            self.last_serviced = {}
            self.cache = SimpleNamespace(bytes_in_use=3, bytes_total=8)

    class Backend:
        def __init__(self, work_s, agent):
            self.engine, self.sessions = Engine(work_s), {agent: 7}

        def step(self):
            eng = self.engine
            time.sleep(eng.work_s)
            eng.tokens_dispatched += eng.max_batch
            eng.tokens_real += 1
            eng.last_serviced = {7: 1}
            eng.reqs[7].out_tokens.append(len(eng.reqs[7].out_tokens))
            eng.reqs[7].num_tokens += 1
            return SimpleNamespace(serviced={7: 1}, finished=[])

    class Fleet:
        def __init__(self):
            self.members = [SimpleNamespace(idx=i, alive=True, backend=b)
                            for i, b in enumerate(
                                [Backend(0.0, "a"), Backend(0.05, "b")])]

        def step(self):
            for ext, m in zip((101, 102), self.members):
                m.backend.step()
            return SimpleNamespace(serviced={101: 1, 102: 1}, finished=[])

    fleet = Fleet()
    rec = harness.Recorder(None)
    wrap = harness.ClientSide(fleet, rec)
    turns = {101: {"agent": "a", "out_ids": [], "tok_t": []},
             102: {"agent": "b", "out_ids": [], "tok_t": []}}
    wrap.live.update(turns)
    for _ in range(2):
        wrap.step()
    assert [s["member"] for s in rec.steps] == [0, 1, 0, 1]
    fast, slow = rec.steps[2], rec.steps[3]
    assert fast["t1"] <= slow["t0"]
    assert fast["t1"] - fast["t0"] < 0.04 <= slow["t1"] - slow["t0"]
    assert slow == {**slow, "C": 1, "real": 1, "rows": [(11, 1)],
                    "kv_used": 3, "kv_total": 8}
    assert [t["out_ids"] for t in turns.values()] == [[0, 1], [0, 1]]
    assert wrap.placement() == {"a": 0, "b": 1}


def test_the_client_side_hands_on_the_fleets_own_surface():
    """The dispatcher finds what it finds on the fleet itself: its
    members and rebalance hook, and no ``idle_sessions`` where the fleet
    has none."""
    class Engine:
        reqs = {}

    class Member:
        def __init__(self, i):
            self.idx, self.backend = i, SimpleNamespace(
                engine=Engine(), sessions={}, step=lambda: None)

    class Fleet:
        obs = None
        members = [Member(0), Member(1)]

        def rebalance_for_admission(self, agent_id, prompt):
            return (agent_id, prompt)

    fleet = Fleet()
    wrap = harness.ClientSide(fleet, harness.Recorder(None))
    assert wrap.members is fleet.members and wrap.fleet is fleet
    assert [b.engine for b in wrap.parts] == \
        [m.backend.engine for m in fleet.members]
    assert getattr(wrap, "idle_sessions", None) is None
    assert wrap.rebalance_for_admission("a", "p") == ("a", "p")
    single = SimpleNamespace(engine=Engine(), obs=None, sessions={},
                             step=lambda: None,
                             idle_sessions=lambda: [("a", 1, 2)])
    wrap = harness.ClientSide(single, harness.Recorder(None))
    assert wrap.parts == [single] and wrap.fleet is None
    assert wrap.idle_sessions() == [("a", 1, 2)]


def test_every_named_file_of_the_benchmark_exists():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    for w in spec["workloads"]:
        cell = harness.load_cell(w["name"], spec)
        assert cell.per_layer and cell.end_to_end
        for m in cell.per_layer:
            assert callable(harness.metric_reader(m["name"], cell.roots))
    for c in spec["configs"]:
        assert os.path.isfile(os.path.join(REPO, c["file"]))


# ------------------------------------------------ architecture modules
CONFIGS = sorted(glob.glob(os.path.join(harness.BENCH, "configs", "*.json"))
                 + glob.glob(os.path.join(DATA, "configs", "*.json")))


@pytest.mark.parametrize("path", CONFIGS, ids=os.path.basename)
def test_every_config_names_an_arch_that_matches_the_program(path):
    """The module exists and defines the contract, and its parameter tree
    is the program's at the file's own sizes (shapes only, no arrays)."""
    from repro.models import abstract_params
    with open(path) as f:
        config = json.load(f)
    arch = harness.config_arch(config, path, (DATA, harness.BENCH))
    assert all(callable(getattr(arch, n)) for n in harness.ARCH_API)
    d = arch.dims(config)
    want = jax.tree.map(lambda s: (s.shape, s.dtype),
                        abstract_params(arch.program_config(config, d)))
    have = jax.tree.map(lambda s: (s, np.dtype(d["dtype"])),
                        arch.param_shapes(d),
                        is_leaf=lambda x: isinstance(x, tuple))
    assert want == have


@pytest.mark.parametrize("arch", [None, "no-such-arch"],
                         ids=["arch_key_missing", "arch_module_missing"])
def test_a_config_without_its_arch_module_is_refused(tmp_path, arch):
    (tmp_path / "configs").mkdir()
    cfg = model.load("configs", "tiny", roots=(DATA,))
    cfg.pop("arch")
    if arch is not None:
        cfg["arch"] = arch
    path = tmp_path / "configs" / "lost.json"
    path.write_text(json.dumps(cfg))
    spec = {"workloads": [{"name": "lost.short-chat", "config": "lost",
                           "traffic": "short-chat", "chips": 1, "why": "t"}],
            "end_to_end": [], "per_layer": []}
    with pytest.raises(harness.HarnessError, match=re.escape(str(path))):
        harness.load_cell("lost.short-chat", spec,
                          roots=(str(tmp_path), harness.BENCH))


def _golden():
    with open(os.path.join(DATA, "gqa_golden.json")) as f:
        return json.load(f)


def test_gqa_weights_are_the_recorded_ones():
    g = _golden()["weights"]
    d = GQA.dims(model.load("configs", g["config"], roots=(DATA,)))
    params = model.make_params(GQA.param_shapes(d), d["dtype"], g["seed"])
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)}:{a.shape}:{a.dtype}"
                 .encode())
        h.update(a.tobytes())
    assert h.hexdigest() == g["sha256"]


@pytest.mark.parametrize("control", [False, True], ids=["plain", "control"])
def test_gqa_reference_gives_the_recorded_logits(control):
    g = _golden()["reference"]
    d = GQA.dims(model.load("configs", g["config"], roots=(DATA,)))
    params = model.make_params(GQA.param_shapes(d), d["dtype"], g["seed"])
    mx, picked, arg = reference._run(GQA, d, control, params,
                                     np.asarray(g["tokens"], np.int32),
                                     None, g["cache_len"])
    want = g["control" if control else "plain"]
    np.testing.assert_array_equal(mx, np.float32(want["max_logit"]))
    np.testing.assert_array_equal(picked, np.float32(want["picked"]))
    np.testing.assert_array_equal(arg, np.int32(want["argmax"]))


def test_gqa_work_counts_are_the_recorded_ones():
    g = _golden()["work"]
    d = GQA.dims(model.load("configs", g["config"]))
    rows = [tuple(r) for r in g["rows"]]
    counts = flops.counts(GQA)
    assert list(counts.paged_attn(d, rows)) == g["paged_attn"]
    assert counts.model_flops(d, rows) == g["model_flops"]
    assert counts.least_time is flops.least_time


@pytest.mark.parametrize("name", ["chatglm3-6b"])
def test_configs_keep_published_widths(name):
    path = model.find("configs", name)
    config = model.load("configs", name)
    arch = harness.config_arch(config, path)
    d = arch.dims(config)
    assert d["head_dim"] == 128 and d["n_heads"] % d["n_kv_heads"] == 0
    shapes = arch.param_shapes(d)
    n = sum(math.prod(s) for s in jax.tree_util.tree_leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))
    want = {"chatglm3-6b": 6.24e9}[name]
    assert n == pytest.approx(want, rel=0.01)
