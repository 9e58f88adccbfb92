"""Subprocess driver for tests/test_sharded_megastep.py (leading
underscore: not collected by pytest).

XLA's device count must be forced BEFORE jax initialises, and the pytest
process has long since imported jax — so every device-backed sharded-
megastep scenario runs here, in one fresh interpreter on 4 virtual CPU
devices, and the results come back as a single JSON report on stdout.

The model is f32 on purpose: the parity oracle is exact token equality,
and at tp>1 the per-layer psum's different reduction order costs a bf16
ulp per layer — enough to flip a greedy argmax even though the math is
right (DESIGN.md §13). At f32 every mesh width reproduces the single-
device tokens exactly, and TP=1 is bitwise identical in the pools.
"""
import json
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax                                                  # noqa: E402
import numpy as np                                          # noqa: E402

from repro.configs import get_smoke_config                  # noqa: E402
from repro.core.context.tiers import KVSwapStore            # noqa: E402
from repro.launch.mesh import make_tp_mesh                  # noqa: E402
from repro.models import build                              # noqa: E402
from repro.serving import PagedInferenceEngine              # noqa: E402

# hkv=4 shards across up to 4 devices; g=2 (8 q heads over 4 kv heads)
# exercises the tiled-GQA head permutation nontrivially
CFG = get_smoke_config("gemma-2b").replace(
    remat=False, n_layers=2, d_model=64, n_heads=8, n_kv_heads=4,
    head_dim=16, d_ff=128, vocab_size=256, compute_dtype="float32")
ENGINE_KW = dict(num_blocks=65, block_size=8, max_batch=4, max_len=96,
                 prefill_chunk=16, token_budget=16)
PROMPT = np.arange(1, 20, dtype=np.int32)
EXTEND = np.arange(30, 38, dtype=np.int32)

PARAMS = build(CFG).init_params(jax.random.PRNGKey(0))


def engine(mesh=None, store=None):
    return PagedInferenceEngine(CFG, PARAMS, mesh=mesh, swap_store=store,
                                **ENGINE_KW)


def drive(eng):
    """submit+retain -> drain -> extend -> drain: two greedy turns on one
    retained session. Deterministic, so the token ids ARE the oracle."""
    rid = eng.submit(PROMPT, max_new_tokens=8, retain=True)
    eng.run_to_completion()
    t1 = [int(t) for t in eng.reqs[rid].out_tokens]
    eng.extend(rid, EXTEND, max_new_tokens=8)
    eng.run_to_completion()
    return rid, t1 + [int(t) for t in eng.reqs[rid].out_tokens]


def live_pools(eng):
    """Full-hkv host copies of both pools EXCLUDING the null block: block 0
    absorbs masked scatter writes whose ordering legitimately differs
    between the single-device and shard_map lowerings."""
    return np.asarray(eng.cache.k[:, 1:]), np.asarray(eng.cache.v[:, 1:])


report = {"devices": jax.device_count()}

# ---- single-device reference ---------------------------------------------
ref_eng = engine()
_, ref_toks = drive(ref_eng)
ref_k, ref_v = live_pools(ref_eng)
report["ref_tokens"] = ref_toks

# ---- parity + contracts at every mesh width ------------------------------
for tp in (1, 2, 4):
    eng = engine(mesh=make_tp_mesh(tp))
    _, toks = drive(eng)
    st = eng.step_stats()
    k, v = live_pools(eng)
    report[f"tp{tp}"] = {
        "tokens": toks,
        "tokens_equal": bool(toks == ref_toks),
        "pools_bitwise": bool(np.array_equal(k, ref_k)
                              and np.array_equal(v, ref_v)),
        "jit_dispatches_per_step": st["jit_dispatches_per_step"],
        "host_transfer_bytes_per_step": st["host_transfer_bytes_per_step"],
        "trace_buckets": list(st["trace_buckets"]),
        "bucket_set": list(st["bucket_set"]),
        "tp": st["tp"],
    }

# ---- hibernate at TP=2, wake at TP=4 -------------------------------------
# Hibernation payloads are host-side full-hkv pages (pool.gather assembles
# the sharded array), so they are mesh-shape-agnostic: a session parked
# under one mesh must continue bit-exactly under another.
store = KVSwapStore()
a = engine(mesh=make_tp_mesh(2), store=store)
rid = a.submit(PROMPT, max_new_tokens=8, retain=True)
a.run_to_completion()
turn1 = [int(t) for t in a.reqs[rid].out_tokens]
a.hibernate(rid)
stored_after_hibernate = len(store)   # the SHARED store must hold it (the
# engine would silently use a private store if SwapManager truthiness-
# tested the empty KVSwapStore — the regression this line guards)
b = engine(mesh=make_tp_mesh(4), store=store)
b.reqs[rid] = a.reqs[rid]          # adopt the swapped session wholesale
b._next_rid = rid + 1
b.extend(rid, EXTEND, max_new_tokens=8)
b.run_to_completion()
turn2 = [int(t) for t in b.reqs[rid].out_tokens]
report["hibernate"] = {
    "stored_after_hibernate": stored_after_hibernate,
    "turn1_equal": bool(turn1 == ref_toks[:8]),
    "turn2_equal": bool(turn2 == ref_toks[8:]),
    "turn2": turn2,
}

# ---- engine-loss journal failover: commit at TP=2, restore at TP=4 ------
# The write-ahead journal commits full-hkv host pages (export_session
# gathers the sharded pool before serialising), so a session journaled by
# an engine on one mesh restores bit-exactly on a survivor with a
# DIFFERENT mesh — the fleet's engine-loss failover story beyond tp=1.
import tempfile                                             # noqa: E402

from repro.serving import SessionJournal                    # noqa: E402

journal = SessionJournal(tempfile.mkdtemp())
a = engine(mesh=make_tp_mesh(2))
rid = a.submit(PROMPT, max_new_tokens=8, retain=True)
a.run_to_completion()
jf_turn1 = [int(t) for t in a.reqs[rid].out_tokens]
payload = a.export_session(rid)
if payload is None:             # only coherent between turns: park first
    a.park(rid)
    payload = a.export_session(rid)
journal.commit("agent-x", payload)
del a                           # the tp=2 engine "dies" with its pages
b = engine(mesh=make_tp_mesh(4))
restored = journal.load("agent-x")
rid2 = b.restore_session(restored)
b.extend(rid2, EXTEND, max_new_tokens=8)
b.run_to_completion()
jf_turn2 = [int(t) for t in b.reqs[rid2].out_tokens]
report["journal_failover"] = {
    "committed": payload is not None and restored is not None,
    "turn1_equal": bool(jf_turn1 == ref_toks[:8]),
    "turn2_equal": bool(jf_turn2 == ref_toks[8:]),
    "turn2": jf_turn2,
}

# ---- recompile guard under a mesh ----------------------------------------
# varied prompt lengths through the budgeted pack: every traced width must
# come from the bounded pow2 bucket set, mesh or not
eng = engine(mesh=make_tp_mesh(2))
eng.compile_buckets()
for i in range(3):
    eng.submit(np.arange(1, 8 + 5 * i, dtype=np.int32), max_new_tokens=4)
eng.run_to_completion()
st = eng.step_stats()
report["bucket_guard"] = {
    "trace_buckets": list(st["trace_buckets"]),
    "bucket_set": list(st["bucket_set"]),
    "within": bool(set(st["trace_buckets"]) <= set(st["bucket_set"])),
    "jit_dispatches_per_step": st["jit_dispatches_per_step"],
}

print(json.dumps(report))
