"""Plain reference of the served model, and its lower-precision control.

A straightforward decoder in ``jax.numpy``: RMSNorm, rotary attention with
grouped KV heads, a SwiGLU or tanh-GELU MLP, an untied LM head. It runs in
the configuration's stated precision, bfloat16 weights and activations
with float32 accumulation, softmax and norms; attention scores and their
weighted sum are float32 at the highest matmul precision. It imports
nothing of the served program and is given only the weights the benchmark
made. Query head ``h`` reads kv head ``h mod n_kv_heads``, and the rotary
dims are split in halves, as the configuration files note.

A sequence is run in blocks of ``CHUNK`` query rows against a contiguous
KV cache, so one compiled program serves every length and the whole
context never sits in one attention matrix.

The control is the same reference with its weights and KV cache in float8
(e4m3), the nearest precision below the configuration's bfloat16: every
weight matrix rounded with one scale per output column (the embedding
with one per row), and every K and V written to the cache rounded.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence

import numpy as np

CHUNK = 512
FP8_MAX = 448.0


def _jnp():
    import jax
    import jax.numpy as jnp
    return jax, jnp


def fp8_round(w, axis: int = -2):
    """Round ``w`` to float8 e4m3 with one absmax scale per slice along
    ``axis`` (the input axis of a projection: one scale per output)."""
    jax, jnp = _jnp()
    wf = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(wf), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s == 0, 1.0, s)
    q = (wf / s).astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return (q * s).astype(w.dtype)


def _forward_chunk(d: Dict, control: bool, params, kc, vc, toks, nxt, start):
    """One block of ``CHUNK`` positions: writes their K/V at ``start`` and
    returns (max logit, logit of ``nxt``, argmax) per position."""
    jax, jnp = _jnp()
    f32, ct = jnp.float32, jnp.dtype(d["dtype"])
    hq, hkv, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    g, rot, eps = hq // hkv, d["rotary_dims"], d["norm_eps"]
    T, S = toks.shape[0], kc.shape[1]
    hi = jax.lax.Precision.HIGHEST
    w = fp8_round if control else (lambda x, axis=-2: x)

    def mm(x, wt):
        return jnp.matmul(x, w(wt), preferred_element_type=f32).astype(ct)

    def rms(x, gain):
        xf = x.astype(f32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
        return (y * (1.0 + gain.astype(f32))).astype(ct)

    pos = start + jnp.arange(T)
    inv = 1.0 / (d["rope_theta"] ** (jnp.arange(0, rot, 2, dtype=f32) / rot))
    ang = pos.astype(f32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(x):
        xf = x.astype(f32)
        x1, x2 = xf[..., :rot // 2], xf[..., rot // 2:rot]
        out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                               xf[..., rot:]], axis=-1)
        return out.astype(ct)

    causal = jnp.arange(S)[None, :] <= pos[:, None]            # (T, S)

    def layer(x, xs):
        lp, k_l, v_l = xs
        a = lp["attn"]
        h = rms(x, lp["attn_norm"])
        q = rope(mm(h, a["wq"]).reshape(T, hq, hd))
        k = rope(mm(h, a["wk"]).reshape(T, hkv, hd))
        v = mm(h, a["wv"]).reshape(T, hkv, hd)
        if control:
            k = k.astype(jnp.float8_e4m3fn).astype(ct)
            v = v.astype(jnp.float8_e4m3fn).astype(ct)
        k_l = jax.lax.dynamic_update_slice(k_l, k, (start, 0, 0))
        v_l = jax.lax.dynamic_update_slice(v_l, v, (start, 0, 0))
        qg = q.reshape(T, g, hkv, hd).astype(f32)
        s = jnp.einsum("tgkd,skd->gkts", qg, k_l.astype(f32),
                       precision=hi) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("gkts,skd->tgkd", p, v_l.astype(f32), precision=hi)
        x = x + mm(o.reshape(T, hq * hd).astype(ct), a["wo"])
        m = lp["mlp"]
        h = rms(x, lp["mlp_norm"])
        if d["mlp"] == "swiglu":
            u = jax.nn.silu(mm(h, m["w_gate"]).astype(f32)) \
                * mm(h, m["w_up"]).astype(f32)
        else:
            u = jax.nn.gelu(mm(h, m["w_up"]).astype(f32), approximate=True)
        x = x + mm(u.astype(ct), m["w_down"])
        return x, (k_l, v_l)

    emb = fp8_round(params["embed"], axis=-1) if control else params["embed"]
    x = jnp.take(emb, toks, axis=0).astype(ct)
    x, (kc, vc) = jax.lax.scan(layer, x, (params["layers"], kc, vc))
    x = rms(x, params["final_norm"])
    logits = jnp.matmul(x, w(params["lm_head"]), preferred_element_type=f32)
    picked = jnp.take_along_axis(logits, nxt[:, None], axis=1)[:, 0]
    return (kc, vc, jnp.max(logits, -1), picked,
            jnp.argmax(logits, -1).astype(jnp.int32))


@functools.lru_cache(maxsize=None)
def _compiled(key, control: bool):
    jax, _ = _jnp()
    d = dict(key)
    return jax.jit(functools.partial(_forward_chunk, d, control),
                   donate_argnums=(1, 2))


def _run(d: Dict, control: bool, params, toks: np.ndarray, want,
         cache_len: int):
    """Max logit, logit of ``want`` (the token to score at each position;
    by default the next one in ``toks``) and argmax at every position of
    ``toks`` but the last."""
    jax, jnp = _jnp()
    fn = _compiled(tuple(sorted(d.items())), control)
    n = len(toks)
    shape = (d["n_layers"], cache_len, d["n_kv_heads"], d["head_dim"])
    kc = jnp.zeros(shape, jnp.dtype(d["dtype"]))
    vc = jnp.zeros(shape, jnp.dtype(d["dtype"]))
    pick = np.zeros(cache_len, np.int32)
    pick[:n - 1] = toks[1:] if want is None else want
    pad = np.zeros(cache_len, np.int32)
    pad[:n] = toks
    outs = []
    for start in range(0, n - 1, CHUNK):
        kc, vc, *out = fn(params, kc, vc,
                          jnp.asarray(pad[start:start + CHUNK]),
                          jnp.asarray(pick[start:start + CHUNK]),
                          jnp.int32(start))
        outs.append(out)
    return tuple(np.concatenate([np.asarray(o[i]) for o in outs])[:n - 1]
                 for i in range(3))


def compare(d: Dict, params, sequences: Sequence, max_len: int,
            control: bool = False) -> Dict:
    """Check served tokens against the reference.

    ``sequences``: (tokens, served) pairs; ``tokens`` is a session's whole
    token stream (prompts and served tokens in the order the engine wrote
    them) and ``served`` a boolean mask over it, true where the engine
    produced the token. Returns the widest gap by which a served token's
    reference logit lies below the reference's best, and the share of
    served tokens that are the reference's argmax.

    With ``control`` the float8 control takes the program's place: at each
    served position the token it puts first is scored instead of the
    served one, and its widest gap is returned as ``max_logit_gap``; the
    served tokens' own gap is returned as ``program_max_logit_gap``."""
    cache_len = -(-(max_len + 1) // CHUNK) * CHUNK
    gap = ctl_gap = 0.0
    n_served = n_agree = 0
    for toks, served in sequences:
        toks = np.asarray(toks, np.int32)
        at = np.asarray(served, bool)[1:]           # predicted from pos - 1
        if not at.any():
            continue
        mx, picked, arg = _run(d, False, params, toks, None,
                                  cache_len)
        gap = max(gap, float((mx - picked)[at].max()))
        n_served += int(at.sum())
        n_agree += int((arg == toks[1:])[at].sum())
        if control:
            _, _, carg = _run(d, True, params, toks, None, cache_len)
            _, ref_at_ctl, _ = _run(d, False, params, toks, carg, cache_len)
            ctl_gap = max(ctl_gap, float((mx - ref_at_ctl)[at].max()))
    out = {"max_logit_gap": ctl_gap if control else gap,
           "served_checked": n_served,
           "argmax_agreement": n_agree / max(n_served, 1)}
    if control:
        out["program_max_logit_gap"] = gap
    return out


def session_sequences(turns: List[Dict]) -> List:
    """Token streams of a session's finished turns, in the order the engine
    wrote them: each turn's prompt tokens, then its served tokens."""
    toks, served = [], []
    for t in turns:
        toks += list(t["prompt_ids"])
        served += [False] * len(t["prompt_ids"])
        toks += list(t["out_ids"])
        served += [True] * len(t["out_ids"])
    return toks, served
