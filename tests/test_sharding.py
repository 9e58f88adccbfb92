"""Sharding rules + dry-run integration (subprocess: needs 512 host devices,
which must be forced before jax initialises)."""
import json
import os
import subprocess
import sys

import jax
import pytest

from repro.configs import get_config
from repro.distributed.sharding import batch_axes, param_pspec
from repro.models import abstract_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class FakeMesh:
    shape = {"data": 16, "model": 16}


def _pspecs(arch):
    cfg = get_config(arch)
    params = abstract_params(cfg)
    out = {}
    def visit(path, leaf):
        name = "/".join(str(getattr(k, "key", "?")) for k in path)
        out[name] = param_pspec(cfg, FakeMesh(), path, leaf)
        return leaf
    jax.tree_util.tree_map_with_path(visit, params)
    return out


def test_param_rules_2d_shard_big_matrices():
    specs = _pspecs("deepseek-67b")
    assert specs["embed"] == jax.sharding.PartitionSpec("model", "data")
    # scanned layer weights: leading None then (data, model)
    wq = specs["layers/attn/wq"]
    assert wq[0] is None and wq[1] == "data" and wq[2] == "model"
    # norms replicated (all-None spec)
    assert all(ax is None for ax in specs["final_norm"])


def test_kv_proj_replicated_when_heads_indivisible():
    specs = _pspecs("chatglm3-6b")        # hkv=2 < 16
    wk = specs["layers/attn/wk"]
    assert wk[-1] is None, "kv projection must not shard over model"
    specs64 = _pspecs("deepseek-67b")     # hkv=8 < 16 -> also replicated
    assert specs64["layers/attn/wk"][-1] is None


def test_moe_experts_on_model_axis():
    specs = _pspecs("llama4-scout-17b-a16e")
    wg = specs["layers/moe/w_gate"]
    assert wg[-3] == "model" and wg[-2] == "data"


def test_batch_axes_divisibility():
    assert batch_axes(FakeMesh(), 256) == ("data",)
    assert batch_axes(FakeMesh(), 1) is None


# ---------------------------------------------------------------------------
# Serving rules: the sharded megastep's tensor-parallel pspecs (DESIGN.md
# §13). Pure pspec/permutation math — no devices needed; the device-backed
# end-to-end parity runs live in tests/test_sharded_megastep.py.
# ---------------------------------------------------------------------------

import numpy as np

from repro.configs import get_smoke_config
from repro.distributed.sharding import (kv_pool_pspec, megastep_input_pspecs,
                                        megastep_output_pspec,
                                        serving_param_pspecs, tp_head_order,
                                        validate_tp)

P = jax.sharding.PartitionSpec


def _tp_cfg(**kw):
    base = dict(n_layers=2, d_model=64, n_heads=8, n_kv_heads=4,
                head_dim=16, d_ff=128, vocab_size=256)
    base.update(kw)
    return get_smoke_config("gemma-2b").replace(**base)


def test_megastep_pspecs_shapes():
    # pool: (L, num_blocks, hkv, blk, hd) — ONLY the head axis is sharded
    assert kv_pool_pspec() == P(None, None, "tp", None, None)
    # row inputs and the sampled-token output are fully replicated: the
    # per-layer psum restores full activations on every shard, so only one
    # (max_batch,) int32 crosses to host — same bytes as single-device
    assert all(s == P() for s in megastep_input_pspecs())
    assert megastep_output_pspec() == P()


def test_serving_param_pspecs_round_trip():
    cfg = _tp_cfg()
    params = abstract_params(cfg)
    specs = serving_param_pspecs(cfg, 4, params)
    lay = specs["layers"]["attn"]
    # scanned leaves are (L, ...): leading None, then the serving rule
    assert lay["wq"] == P(None, None, "tp")
    assert lay["wk"] == P(None, None, "tp")
    assert lay["wv"] == P(None, None, "tp")
    assert lay["wo"] == P(None, "tp", None)
    # everything else replicates (embed, norms, MLP, lm_head)
    assert specs["embed"] == P()
    assert specs["layers"]["mlp"]["w_gate"] == P()
    assert specs["layers"]["attn_norm"] == P()


def test_serving_param_pspecs_strict_on_indivisible():
    cfg = _tp_cfg()
    params = abstract_params(cfg)
    # tp=3 divides neither hq*hd=128 nor hkv*hd=64 — the serving rules must
    # REFUSE (silent replication would give wrong per-shard shapes inside
    # the shard_map body)
    with pytest.raises(ValueError, match="not divisible"):
        serving_param_pspecs(cfg, 3, params)


def test_validate_tp_errors():
    cfg = _tp_cfg()                      # hq=8, hkv=4
    validate_tp(cfg, 1)
    validate_tp(cfg, 2)
    validate_tp(cfg, 4)
    with pytest.raises(ValueError, match="must be >= 1"):
        validate_tp(cfg, 0)
    with pytest.raises(ValueError, match="n_kv_heads"):
        validate_tp(cfg, 3)
    # (tp | hkv implies tp | hq for any integral-group GQA config, so the
    # n_heads check only fires on malformed configs — not tested here)


def test_tp_head_order_identity_cases():
    cfg = _tp_cfg()
    assert tp_head_order(cfg, 1) is None         # identity => TP=1 mesh is
    # bitwise identical to the single-device engine
    assert tp_head_order(cfg.replace(gqa_mode="grouped"), 2) is None


def test_tp_head_order_is_local_gqa_pairing():
    """The permutation's contract: shard i's contiguous slice of reordered
    q heads, paired locally g-major against shard i's kv slice, must
    reproduce the GLOBAL tiled pairing q head h <-> kv head h % hkv."""
    for tp in (2, 4):
        cfg = _tp_cfg()
        hq, hkv = cfg.n_heads, cfg.n_kv_heads
        order = tp_head_order(cfg, tp)
        assert sorted(order) == list(range(hq))  # a permutation
        hq_l, hkv_l = hq // tp, hkv // tp
        for i in range(tp):
            local = order[i * hq_l:(i + 1) * hq_l]
            for j, h in enumerate(local):
                # local g-major pairing against the shard's kv slice
                local_kv = i * hkv_l + j % hkv_l
                assert h % hkv == local_kv, (tp, i, j, h)


@pytest.mark.slow
def test_dryrun_cell_compiles_in_subprocess():
    """One real lower+compile on the 16x16 production mesh."""
    out = os.path.join("/tmp", "dryrun_test")
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch",
         "whisper-tiny", "--shape", "decode_32k", "--mesh", "single",
         "--out", out],
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
        capture_output=True, text=True, timeout=540)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    with open(os.path.join(out, "whisper-tiny__decode_32k__single.json")) as f:
        cell = json.load(f)
    assert cell["ok"] and cell["n_devices"] == 256
    assert cell["hlo_flops_per_device"] > 0
