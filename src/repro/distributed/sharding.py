"""Sharding rules: 2-D (TP x FSDP) parameter layout, EP for MoE experts,
sequence-sharded KV caches for decode, batch over (pod, data).

Rules are name-based over pytree paths; every rule specifies the trailing
dims, and leading stack dims (scanned layers / hybrid groups) get None
prepended automatically. Dims that don't divide the mesh axis stay
unsharded (never silently uneven).

Two rule families live here:

  * training/dry-run rules over the (pod, data, model) mesh —
    ``param_pspec`` and friends, used by the launcher and the dry-run;
  * serving rules over the 1-D ``("tp",)`` mesh the sharded megastep runs
    on — ``serving_param_pspecs`` / ``kv_pool_pspec`` / the head
    permutation. These are STRICT (a dim that doesn't divide ``tp`` is a
    ``ValueError``, never a silent replication): shard_map in_specs must
    match the placement exactly or the per-shard shapes inside the body
    are wrong.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig

DP, MP, POD = "data", "model", "pod"
TP = "tp"                     # the serving megastep's tensor-parallel axis

# rule table: path-regex -> trailing-dims spec template using DP/MP markers.
# the first matching rule wins.
_PARAM_RULES = [
    (r"embed$", (MP, DP)),
    (r"lm_head$", (DP, MP)),
    (r"(dec_pos|enc_pos)$", (None, DP)),
    (r"(kv_norm|norm|attn_norm|mlp_norm|cross_norm|final_norm|enc_final_norm)$",
     (None,)),
    # attention
    (r"attn/w(q|k|v)$", (DP, MP)),
    (r"cross/w(q|k|v)$", (DP, MP)),
    (r"(attn|cross)/wo$", (MP, DP)),
    # MLA
    (r"attn/w_dkv$", (DP, None)),
    (r"attn/w_u(k|v)$", (None, MP)),
    # dense MLP
    (r"mlp/w_(gate|up)$", (DP, MP)),
    (r"mlp/w_down$", (MP, DP)),
    # MoE: experts over MP (expert parallelism), router replicated-on-MP
    (r"moe/router$", (DP, None)),
    (r"moe/w_(gate|up)$", (MP, DP, None)),
    (r"moe/w_down$", (MP, None, DP)),
    (r"moe/shared/w_(gate|up)$", (DP, MP)),
    (r"moe/shared/w_down$", (MP, DP)),
    # Mamba
    (r"mamba/in_proj$", (DP, MP)),
    (r"mamba/conv_w$", (None, MP)),
    (r"mamba/conv_b$", (MP,)),
    (r"mamba/(A_log|D|dt_bias)$", (MP,)),
    (r"mamba/out_proj$", (MP, DP)),
]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
    return "/".join(parts)


def _axis_ok(mesh: Mesh, axis: Optional[str], dim: int) -> Optional[str]:
    if axis is None:
        return None
    return axis if dim % mesh.shape[axis] == 0 else None


def param_pspec(cfg: ModelConfig, mesh: Mesh, path, leaf) -> P:
    name = _path_str(path)
    # kv projections: a 16-way shard of hkv*hd is only expressible as a
    # head-major tiling when hkv divides the mesh axis; otherwise GSPMD
    # must all-gather at the (hkv, hd) reshape and the whole attention
    # computation replicates (EXPERIMENTS.md §Perf A1). Replicating the
    # small kv projection across `model` avoids that.
    if re.search(r"attn/w(k|v)$", name) and cfg.n_kv_heads and             cfg.n_kv_heads % mesh.shape[MP] != 0:
        t = 2
        lead = (None,) * (leaf.ndim - t)
        return P(*(lead + (_axis_ok(mesh, DP, leaf.shape[-2]), None)))
    for pat, template in _PARAM_RULES:
        if re.search(pat, name):
            t = len(template)
            lead = (None,) * (leaf.ndim - t)
            dims = tuple(
                _axis_ok(mesh, ax, leaf.shape[leaf.ndim - t + i])
                for i, ax in enumerate(template))
            return P(*(lead + dims))
    return P()                       # replicate by default (norm scales etc.)


def param_shardings(cfg: ModelConfig, mesh: Mesh, params_tree) -> Any:
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, param_pspec(cfg, mesh, path,
                                                           leaf)),
        params_tree)


def batch_axes(mesh: Mesh, batch: int):
    """Largest (pod?, data) product that divides the global batch."""
    axes = []
    if POD in mesh.shape:
        if batch % (mesh.shape[POD] * mesh.shape[DP]) == 0:
            return (POD, DP)
        if batch % mesh.shape[POD] == 0:
            return (POD,)
    if batch % mesh.shape[DP] == 0:
        return (DP,)
    return None


def batch_shardings(cfg: ModelConfig, mesh: Mesh, specs: Dict[str, Any]) -> Any:
    out = {}
    for k, sds in specs.items():
        if sds.ndim == 0:
            out[k] = NamedSharding(mesh, P())
            continue
        ba = batch_axes(mesh, sds.shape[0])
        out[k] = NamedSharding(mesh, P(ba, *([None] * (sds.ndim - 1))))
    return out


def decode_state_pspec(cfg: ModelConfig, mesh: Mesh, path, leaf) -> P:
    """KV caches: batch over data, sequence over model (sequence-parallel
    cache — the KV tensor is the dominant decode working set). SSM states:
    heads/channels over model."""
    name = _path_str(path)
    ba = None
    # locate the batch dim: stacked layer caches are (L, B, ...) or hybrid
    # (G, E, B, ...); whisper cross caches (L, B, S, h, hd)
    def spec_for(dims_after_stack, batch_pos):
        lead = [None] * batch_pos
        b = leaf.shape[batch_pos]
        lead.append(batch_axes(mesh, b) and DP if b % mesh.shape[DP] == 0
                    else None)
        rest = [None] * (leaf.ndim - batch_pos - 1)
        return lead, rest

    if re.search(r"(^|/)(k|v|ckv|krope|cross_k|cross_v|attn_k|attn_v)\d?$",
                 name):
        stack = 1 if not name.startswith(("ckv0", "krope0")) else 0
        if name in ("ckv0", "krope0"):
            stack = 0
        lead, rest = spec_for(None, stack)
        # sequence dim right after batch
        seq_idx = stack + 1
        rest = [None] * (leaf.ndim - stack - 1)
        if leaf.shape[seq_idx] % mesh.shape[MP] == 0:
            rest[0] = MP
        return P(*(lead + rest))
    if re.search(r"(conv|tail_conv)$", name):
        spec = [None] * leaf.ndim
        if leaf.shape[-1] % mesh.shape[MP] == 0:
            spec[-1] = MP
        b_idx = leaf.ndim - 3
        if leaf.shape[b_idx] % mesh.shape[DP] == 0:
            spec[b_idx] = DP
        return P(*spec)
    if re.search(r"(ssm|tail_ssm)$", name):
        # (..., B, g, hg, n, p): shard hg over model
        spec = [None] * leaf.ndim
        if leaf.shape[-3] % mesh.shape[MP] == 0:
            spec[-3] = MP
        b_idx = leaf.ndim - 5
        if leaf.shape[b_idx] % mesh.shape[DP] == 0:
            spec[b_idx] = DP
        return P(*spec)
    return P()


def decode_state_shardings(cfg: ModelConfig, mesh: Mesh, state_tree) -> Any:
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, decode_state_pspec(cfg, mesh, path, leaf)),
        state_tree)


# --------------------------------------------------------------------------
# Serving: tensor-parallel pspecs for the sharded megastep (DESIGN.md §13)
# --------------------------------------------------------------------------

def kv_pool_pspec() -> P:
    """Paged KV pool ``(L, num_blocks, hkv, blk, hd)``: KV heads over
    ``tp``, everything else local. Block ids (and therefore page tables)
    are shard-invariant — every shard holds the SAME blocks for its own
    head slice, so one host-side page table per sequence drives all
    shards."""
    return P(None, None, TP, None, None)


def megastep_input_pspecs() -> Tuple[P, P, P, P, P]:
    """Megastep row inputs — ``tokens (B, C)``, ``cache_lens (B,)``,
    ``valids (B,)``, ``page_tables (B, npages)``, ``poison_mask (B,)`` —
    are all replicated: every shard sees the full batch and computes its
    head slice of it (so the in-jit finiteness sentinel, like the argmax,
    is computed identically on every shard)."""
    return (P(), P(), P(), P(), P())


def megastep_output_pspec() -> P:
    """The sampled ``(B,)`` int32 vector: replicated. The per-layer
    attention-output ``psum`` over ``tp`` restores full activations on
    every shard, so unembed + argmax are computed identically everywhere
    and only one (B,) vector crosses to host — same bytes as the
    single-device megastep."""
    return P()


def validate_tp(cfg: ModelConfig, tp: int):
    """The divisibility contract behind contiguous per-shard head slices.
    Raised as ValueError so launchers can surface it as a CLI error."""
    if tp < 1:
        raise ValueError(f"tp={tp} must be >= 1")
    if cfg.n_kv_heads % tp:
        raise ValueError(
            f"tp={tp} does not divide n_kv_heads={cfg.n_kv_heads}: the "
            "paged KV pool shards whole KV heads, so tp must divide hkv")
    if cfg.n_heads % tp:
        raise ValueError(
            f"tp={tp} does not divide n_heads={cfg.n_heads}")


def tp_head_order(cfg: ModelConfig, tp: int) -> Optional[List[int]]:
    """Query-head order that makes CONTIGUOUS per-shard column slices of
    ``wq`` (and row slices of ``wo``) reproduce the global GQA pairing.

    Under ``gqa_mode == "tiled"`` the attention path pairs q head ``h``
    with kv head ``h % hkv`` (g-major: heads are laid out group-major, see
    ``simple_attention``). Shard ``i`` owns kv heads
    ``[i*hkv/tp, (i+1)*hkv/tp)``, so its q heads are strided through the
    global head axis; this permutation gathers them contiguous, ordered so
    the LOCAL g-major pairing (against the local kv slice) is exactly the
    global pairing. Identity when ``tp == 1`` — which is what makes the
    TP=1 mesh run bitwise identical to the single-device engine.

    Under ``gqa_mode == "grouped"`` (kv-major: q head ``h`` pairs with kv
    head ``h // g``) contiguous slices already pair correctly — returns
    None (identity)."""
    validate_tp(cfg, tp)
    if tp == 1 or cfg.gqa_mode != "tiled":
        return None
    hq, hkv = cfg.n_heads, cfg.n_kv_heads
    g, hkv_l = hq // hkv, hkv // tp
    return [g_idx * hkv + i * hkv_l + kv_l
            for i in range(tp)
            for g_idx in range(g)
            for kv_l in range(hkv_l)]


def permute_attn_heads(cfg: ModelConfig, tp: int, params):
    """Reorder ``wq`` columns / ``wo`` rows per ``tp_head_order`` so the
    TP sharding below can slice heads contiguously. A pure relabeling of
    the head axis: wq and wo move together, so the composed
    ``(x @ wq) ... @ wo`` is unchanged. No-op (returns ``params``
    unchanged) when the order is the identity."""
    order = tp_head_order(cfg, tp)
    if order is None:
        return params
    hd = cfg.resolved_head_dim
    idx = jnp.asarray(order)

    def fix(path, leaf):
        name = _path_str(path)
        if re.search(r"attn/wq$", name):
            *lead, d, cols = leaf.shape
            w = leaf.reshape(*lead, d, cols // hd, hd)
            return jnp.take(w, idx, axis=len(lead) + 1).reshape(leaf.shape)
        if re.search(r"attn/wo$", name):
            *lead, rows, d = leaf.shape
            w = leaf.reshape(*lead, rows // hd, hd, d)
            return jnp.take(w, idx, axis=len(lead)).reshape(leaf.shape)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, params)


# serving rule table (megastep / GQA family only): wq columns and wo rows
# shard over tp (head-major, post-permutation), wk/wv columns shard over tp
# (whole KV heads — contiguous slices pair correctly in both gqa modes),
# everything else (embed, norms, MLP, lm_head) replicates: replicated
# activations + per-layer attention psum keep every shard's residual
# stream identical, so the in-jit argmax needs no final collective.
_TP_SERVING_RULES = [
    (r"attn/w(q|k|v)$", (None, TP)),
    (r"attn/wo$", (TP, None)),
]


def serving_param_pspec(cfg: ModelConfig, tp: int, path, leaf) -> P:
    name = _path_str(path)
    for pat, template in _TP_SERVING_RULES:
        if re.search(pat, name):
            t = len(template)
            lead = (None,) * (leaf.ndim - t)
            for i, ax in enumerate(template):
                if ax is not None and leaf.shape[leaf.ndim - t + i] % tp:
                    raise ValueError(
                        f"{name}: dim {leaf.shape[leaf.ndim - t + i]} not "
                        f"divisible by tp={tp}")
            return P(*(lead + template))
    return P()


def serving_param_pspecs(cfg: ModelConfig, tp: int, params_tree) -> Any:
    """Pytree of PartitionSpecs over the serving params — used both to
    place the (head-permuted) params and as the shard_map in_specs."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: serving_param_pspec(cfg, tp, path, leaf),
        params_tree)


def shard_serving_params(cfg: ModelConfig, mesh: Mesh, params):
    """Permute attention heads for the mesh's ``tp`` factor and place every
    leaf under the serving rules. Returns ``(placed_params, pspec_tree)``;
    the pspec tree doubles as the megastep's shard_map in_specs."""
    tp = mesh.shape[TP]
    params = permute_attn_heads(cfg, tp, params)
    specs = serving_param_pspecs(cfg, tp, params)
    placed = jax.tree_util.tree_map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)),
        params, specs)
    return placed, specs


# --------------------------------------------------------------------------
# Elastic re-mesh (absorbed from the old distributed/elastic.py stub, which
# duplicated these against a drifting copy of the rules; see ROADMAP #2)
# --------------------------------------------------------------------------

def reshard_params(cfg: ModelConfig, params: Any, mesh) -> Any:
    """Place a (host-resident) param pytree onto ``mesh`` under the
    training rules. Rules degrade gracefully (dims that stop dividing the
    new axis sizes fall back to replication), which is what makes
    shrink-to-fewer-hosts restarts safe."""
    shardings = param_shardings(cfg, mesh, params)
    return jax.tree_util.tree_map(jax.device_put, params, shardings)


def elastic_restore(cfg: ModelConfig, checkpointer, like: Any, mesh,
                    step=None):
    """Restore the latest checkpoint and re-place it on a (possibly
    different) mesh — checkpoints store logically-unsharded arrays, so
    elastic scaling is purely a placement problem.
    Returns (placed_tree, step, extra)."""
    tree, step, extra = checkpointer.restore(like, step=step)
    return reshard_params(cfg, tree, mesh), step, extra


def opt_state_shardings(cfg: ModelConfig, mesh: Mesh, opt_tree, params_tree):
    """Moments mirror the param layout (ZeRO); step scalar replicated."""
    pshard = param_shardings(cfg, mesh, params_tree)

    def like(path, leaf):
        name = _path_str(path)
        if name.startswith("0") or leaf.ndim == 0:     # step counter
            return NamedSharding(mesh, P())
        # m/v/err trees share params' structure under fields 1..3
        return None

    # structure: AdamWState(step, m, v, err)
    import jax.tree_util as jtu
    step_s = NamedSharding(mesh, P())
    m_s = pshard
    v_s = pshard
    err = opt_tree.err
    err_s = pshard if err is not None else None
    from repro.training.optimizer import AdamWState
    return AdamWState(step=step_s, m=m_s, v=v_s, err=err_s)
