"""Token-budget megastep tests (DESIGN.md §11): decode-first packing
invariants (budget never exceeded, decode rows always serviced, no active
row ever starved), bounded pow2 trace buckets, token parity across budget
widths at f32, budget-aware admission accounting, a lone prompt burning
the whole budget in one step, budget validation and the default budget."""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import build
from repro.serving import PagedInferenceEngine, budget_buckets

BLOCK_SIZE = 8
PREFILL_CHUNK = 16


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("gemma-2b").replace(remat=False)
    model = build(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return cfg, params


def _paged(cfg, params, **kw):
    kw.setdefault("num_blocks", 33)
    kw.setdefault("block_size", BLOCK_SIZE)
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_len", 96)
    kw.setdefault("prefill_chunk", PREFILL_CHUNK)
    return PagedInferenceEngine(cfg, params, **kw)


# --------------------------------------------------------------- buckets

def test_budget_bucket_set_is_small_and_pow2():
    """{1} ∪ {8·2^k < budget} ∪ {budget}: bounded at 2 + log2(budget/8)."""
    assert budget_buckets(8) == (1, 8)
    assert budget_buckets(13) == (1, 8, 13)
    assert budget_buckets(64) == (1, 8, 16, 32, 64)
    assert budget_buckets(96) == (1, 8, 16, 32, 64, 96)
    for b in (4, 8, 24, 100, 512):
        bs = budget_buckets(b)
        assert bs[0] == 1 and bs[-1] == b
        assert len(bs) <= 3 + max(0, b - 1).bit_length()


def test_budget_validation(setup):
    """budget < max_batch cannot guarantee a token per row: rejected."""
    cfg, params = setup
    with pytest.raises(ValueError, match="token_budget"):
        _paged(cfg, params, max_batch=4, token_budget=3)
    with pytest.raises(ValueError, match="token_budget"):
        _paged(cfg, params, max_batch=4, token_budget=0)
    # clamped to max_len, not rejected
    eng = _paged(cfg, params, max_batch=4, max_len=96, token_budget=4096)
    assert eng.token_budget == 96


def test_default_budget_gives_every_row_a_chunk(setup):
    """An engine built without a budget takes max_batch * prefill_chunk,
    clamped to max_len: every row of a full batch can advance one whole
    chunk per step, and admission still reserves one chunk. The serving
    CLI without --token-budget builds the same engine."""
    from repro.launch.serve import build_backend, build_parser

    cfg, params = setup
    for kw in (dict(max_batch=4, prefill_chunk=16, max_len=96),
               dict(max_batch=2, prefill_chunk=8, max_len=96),
               dict(max_batch=4, prefill_chunk=32, max_len=96)):
        eng = _paged(cfg, params, **kw)
        assert isinstance(eng.token_budget, int)
        assert eng.token_budget == min(
            kw["max_batch"] * kw["prefill_chunk"], eng.max_len)
        assert eng.bucket_set == budget_buckets(eng.token_budget)
        assert eng.first_chunk_cap == eng.prefill_chunk

    args = build_parser().parse_args(
        ["--arch", "gemma-2b", "--smoke", "--paged", "--max-batch", "2",
         "--prefill-chunk", "8", "--max-len", "64", "--num-blocks", "17",
         "--block-size", "8"])
    served, _ = build_backend(cfg, params, args)
    assert served.token_budget == 16                 # 2 rows x 8-token chunk
    assert served.bucket_set == budget_buckets(16)
    assert served.first_chunk_cap == 8
    assert served.compiled_buckets == set(served.bucket_set)


# --------------------------------------------------------- packing rules

def test_budget_packing_invariants(setup):
    """Every step: total packed tokens <= budget; every decoding row gets
    exactly one token; every prefilling row makes progress (>= 1 token) —
    the budget >= max_batch guarantee means no active row ever starves."""
    cfg, params = setup
    eng = _paged(cfg, params, token_budget=10)
    for i in range(3):
        eng.submit(np.arange(30 + 5 * i) % 50, max_new_tokens=4, retain=True)
    for _ in range(64):
        decoding = [r.rid for r in eng.active.values() if not r.prefilling]
        prefilling = [r.rid for r in eng.active.values() if r.prefilling]
        eng.step()
        assert sum(eng.last_serviced.values()) <= 10
        for rid in decoding:
            assert eng.last_serviced.get(rid) == 1
        for rid in prefilling:
            assert eng.last_serviced.get(rid, 0) >= 1
        if not eng.active and not eng._queue:
            break
    assert not eng.active and not eng._queue


def test_lone_prompt_burns_whole_budget_in_one_step(setup):
    """An empty batch gives its whole budget to the one prefilling row —
    at a chunk-sized budget the same prompt needs ceil(plen/chunk)
    steps."""
    cfg, params = setup
    eng = _paged(cfg, params, token_budget=32, prefill_chunk=8)
    r = eng.submit(np.arange(30) % 50, max_new_tokens=2)
    eng.step()
    assert eng.last_serviced[r] == 30          # whole prompt, one step
    assert max(eng.trace_buckets) == 32        # bucket_for(30) -> 32

    fixed = _paged(cfg, params, token_budget=8, prefill_chunk=8)
    rf = fixed.submit(np.arange(30) % 50, max_new_tokens=2)
    chunks = 0
    while fixed.reqs[rf].prefilling:
        fixed.step()
        chunks += 1
    assert chunks == 4                          # ceil(30 / 8)


def test_full_decode_batch_pays_no_chunk_padding(setup):
    """With every row decoding, the budget pack dispatches at C == 1 —
    decode-only iterations never pay chunk-width FLOPs."""
    cfg, params = setup
    eng = _paged(cfg, params, token_budget=16, prefill_chunk=16)
    for i in range(4):
        eng.submit((np.arange(4) + i) % 50, max_new_tokens=6)
    eng.step()              # 4-token prompts: even split prefills each fully
    buckets_after_prefill = set(eng.trace_buckets)
    real0, disp0 = eng.tokens_real, eng.tokens_dispatched
    eng.step()                                  # all four rows now decode
    assert eng.trace_buckets - buckets_after_prefill <= {1}
    assert eng.tokens_dispatched - disp0 == eng.max_batch  # C == 1
    assert eng.tokens_real - real0 == 4


def test_trace_buckets_bounded_one_dispatch(setup):
    """A mixed multi-turn run only ever traces widths from the bounded
    pow2 bucket set, at exactly one jit dispatch per iteration."""
    cfg, params = setup
    eng = _paged(cfg, params, token_budget=24)
    rids = [eng.submit(np.arange(25 + 7 * i) % 50, max_new_tokens=4,
                       retain=True) for i in range(3)]
    eng.run_to_completion()
    for r in rids:
        eng.extend(r, np.arange(11) % 50, max_new_tokens=4)
    eng.run_to_completion()
    assert eng.trace_buckets <= set(eng.bucket_set)
    assert len(eng.trace_buckets) <= len(eng.bucket_set) == \
        len(budget_buckets(24))
    assert eng.jit_dispatches_per_step == 1.0
    assert eng.jit_dispatches == eng.steps_dispatched > 0


def test_budget_widths_give_equal_tokens_at_f32(setup):
    """At f32 compute the budget changes only the pace, not the model:
    the default budget, an odd one and a whole-prompt one give identical
    greedy tokens, token for token, across a mixed submit+extend run
    (bf16 rounds differently across batch shapes)."""
    cfg, _ = setup
    cfg32 = cfg.replace(compute_dtype="float32")
    params32 = build(cfg32).init_params(jax.random.PRNGKey(0))

    def run(budget):
        eng = _paged(cfg32, params32, token_budget=budget, prefill_chunk=8)
        rids = [eng.submit(np.arange(5 + 7 * i) % 50, max_new_tokens=6,
                           retain=True) for i in range(3)]
        eng.run_to_completion()
        for r in rids:
            eng.extend(r, [3, 4, 5], max_new_tokens=4)
        eng.run_to_completion()
        return {r: eng.reqs[r].out_tokens for r in rids}

    default = run(None)                     # max_batch x prefill_chunk
    assert run(13) == default               # odd budget, ragged buckets
    assert run(96) == default               # whole-prompt-at-once budget


# ------------------------------------------------------------- admission

def test_can_admit_accounts_for_budget_not_chunk(setup):
    """With token_budget < prefill_chunk the first dispatch can write at
    most budget tokens, so admission must only reserve budget-sized
    first-chunk blocks — a chunk-width reservation would bounce a prompt
    the engine can actually take."""
    cfg, params = setup
    # 3 usable blocks; a hot 16-token sequence holds 2 -> 1 block free
    kw = dict(num_blocks=4, block_size=8, max_batch=2, max_len=30,
              prefill_chunk=16)
    wide = _paged(cfg, params, **kw)  # default budget, chunk cap 16
    hot = wide.submit(np.arange(15) % 50, max_new_tokens=2)
    wide.step()                       # whole 15-token prompt in one chunk
    assert wide.reqs[hot].state == "active"
    assert wide.cache.allocator.num_free == 1
    assert not wide.can_admit(16)     # chunk needs 2 pages, only 1 free

    budget = _paged(cfg, params, token_budget=8, **kw)
    hot = budget.submit(np.arange(15) % 50, max_new_tokens=2)
    budget.step()                     # 8 budgeted prompt tokens
    budget.step()                     # remaining 7 -> same 2-page residency
    assert budget.reqs[hot].state == "active"
    assert budget.cache.allocator.num_free == 1
    assert budget.can_admit(16)       # first dispatch writes <= 8 tokens
    r2 = budget.submit(np.arange(6) % 50, max_new_tokens=1)
    done = {r.rid for r in budget.run_to_completion()}
    assert {hot, r2} <= done          # admitted prompt really completes


def test_budget_share_degrades_to_chunk_pace_under_block_pressure(setup):
    """budget > chunk: admission only reserved chunk-cap blocks, so a
    packed share wider than the reservation must find its extra blocks at
    pack time — under block pressure the row degrades to chunk pace for
    the step instead of being OOM-aborted, and catches up once blocks
    free."""
    cfg, params = setup
    # 4 usable blocks; hot holds 2 (14+2 tokens exactly fills them)
    eng = _paged(cfg, params, num_blocks=5, block_size=8, max_batch=2,
                 max_len=32, prefill_chunk=8, token_budget=32)
    hot = eng.submit(np.arange(14) % 50, max_new_tokens=2)
    eng.step()                        # 14-token prompt fits one 32-budget
    # disjoint tokens: no block-aligned prefix for r2 to adopt from hot
    r2 = eng.submit((np.arange(22) + 30) % 50, max_new_tokens=1)
    done = {r.rid for r in eng.step()}
    # r2 wanted its full 31-token share but the pool couldn't grow it:
    # degraded to the 8-token chunk cap, NOT aborted
    assert eng.last_serviced[r2] == 8
    assert not eng.last_failures
    assert not eng.reqs[r2].done
    done |= {r.rid for r in eng.run_to_completion()}
    assert {hot, r2} <= done          # catches up once hot frees its pages


def test_latency_samples_recorded(setup):
    """The engine's TTFT / inter-token samples (what the benchmark's P95
    gates read) are populated and sane."""
    cfg, params = setup
    eng = _paged(cfg, params, token_budget=8)
    eng.submit(np.arange(20) % 50, max_new_tokens=5)
    eng.run_to_completion()
    assert len(eng.ttft_s) == 1                # one turn, one first token
    assert len(eng.itl_s) == 4                 # 5 tokens -> 4 gaps
    assert all(t >= 0 for t in eng.ttft_s + eng.itl_s)
    st = eng.step_stats()
    assert 0.0 <= st["padded_token_fraction"] < 1.0
