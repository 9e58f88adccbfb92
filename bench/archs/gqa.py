"""Dense decoder with grouped-query attention: chatglm3-6b's architecture.

RMSNorm, rotary attention with grouped KV heads, a SwiGLU or tanh-GELU
MLP, an untied LM head; every layer alike, stacked on a leading axis.

``dims`` reads published keys through the configuration file's
``model.keys`` map and takes the rest from its ``model`` block. Query
head ``h`` reads kv head ``h mod n_kv_heads``, and the rotary dims are
split in halves, as the configuration files note.

The plain reference (``forward_chunk``) runs in the configuration's
stated precision, bfloat16 weights and activations with float32
accumulation, softmax and norms; attention scores and their weighted sum
are float32 at the highest matmul precision. Its cache is one K and one V
array per layer, ``(n_layers, cache_len, n_kv_heads, head_dim)``.

The control is the same reference with its weights and KV cache in
float8 (e4m3), the nearest precision below the configuration's bfloat16:
every weight matrix rounded with one scale per output column (the
embedding with one per row), and every K and V written to the cache
rounded.

Work counts use live shapes only: a step is a list of rows ``(cache_len,
q)``, ``q`` real query tokens written after ``cache_len`` resident ones.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, Tuple

from bench.reference import fp8_round

Row = Tuple[int, int]


# ------------------------------------------------------------ the sizes
def dims(spec: Dict) -> Dict:
    """Normalised sizes: n_layers, d_model, n_heads, n_kv_heads, head_dim,
    d_ff, vocab_size, norm_eps, rope_theta, rotary_dims, mlp, dtype."""
    m = spec["model"]
    out = {k: v for k, v in m.items() if k != "keys"}
    for name, key in m.get("keys", {}).items():
        out[name] = spec[key]
    out.setdefault("head_dim", out["d_model"] // out["n_heads"])
    for k in ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
              "d_ff", "vocab_size", "rotary_dims"):
        out[k] = int(out[k])
    out["norm_eps"] = float(out["norm_eps"])
    out["rope_theta"] = float(out["rope_theta"])
    return out


def param_shapes(d: Dict) -> Dict:
    """The served parameter tree: layers stacked on a leading axis."""
    L, D, F, V = d["n_layers"], d["d_model"], d["d_ff"], d["vocab_size"]
    hq, hkv, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    mlp = ({"w_gate": (L, D, F), "w_up": (L, D, F), "w_down": (L, F, D)}
           if d["mlp"] == "swiglu" else
           {"w_up": (L, D, F), "w_down": (L, F, D)})
    return {
        "embed": (V, D), "final_norm": (D,), "lm_head": (D, V),
        "layers": {
            "attn_norm": (L, D), "mlp_norm": (L, D),
            "attn": {"wq": (L, D, hq * hd), "wk": (L, D, hkv * hd),
                     "wv": (L, D, hkv * hd), "wo": (L, hq * hd, D)},
            "mlp": mlp,
        },
    }


def program_config(config: Dict, d: Dict):
    """The served program's ModelConfig for a configuration file."""
    from repro.configs import get_config
    acts = {"swiglu": "silu", "gelu_tanh": "gelu_mlp"}
    return get_config(config["program_arch"]).replace(
        n_layers=d["n_layers"], d_model=d["d_model"], n_heads=d["n_heads"],
        n_kv_heads=d["n_kv_heads"], head_dim=d["head_dim"], d_ff=d["d_ff"],
        vocab_size=d["vocab_size"], act=acts[d["mlp"]],
        rope_theta=d["rope_theta"],
        rotary_pct=d["rotary_dims"] / d["head_dim"], norm_eps=d["norm_eps"],
        tie_embeddings=False, gqa_mode="tiled", remat=False,
        compute_dtype=d["dtype"], param_dtype=d["dtype"])


# ------------------------------------------------------- the reference
def cache_shapes(d: Dict, cache_len: int) -> Dict:
    """The reference's contiguous K/V cache."""
    shape = (d["n_layers"], cache_len, d["n_kv_heads"], d["head_dim"])
    return {"k": shape, "v": shape}


def forward_chunk(d: Dict, control: bool, params, cache, toks, nxt, start):
    """One block of positions: writes their K/V at ``start`` and returns
    (cache, max logit, logit of ``nxt``, argmax) per position."""
    import jax
    import jax.numpy as jnp
    f32, ct = jnp.float32, jnp.dtype(d["dtype"])
    hq, hkv, hd = d["n_heads"], d["n_kv_heads"], d["head_dim"]
    g, rot, eps = hq // hkv, d["rotary_dims"], d["norm_eps"]
    T, S = toks.shape[0], cache["k"].shape[1]
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
    x, (kc, vc) = jax.lax.scan(layer, x,
                               (params["layers"], cache["k"], cache["v"]))
    x = rms(x, params["final_norm"])
    logits = jnp.matmul(x, w(params["lm_head"]), preferred_element_type=f32)
    picked = jnp.take_along_axis(logits, nxt[:, None], axis=1)[:, 0]
    return ({"k": kc, "v": vc}, jnp.max(logits, -1), picked,
            jnp.argmax(logits, -1).astype(jnp.int32))


# --------------------------------------------------------- work counts
def attn_flops_row(d: Dict, cache_len: int, q: int) -> int:
    """One layer's attention for one row: QK^T and PV over the causal
    pairs, ``kv = cache_len + q`` live positions; each pair costs
    ``2 * head_dim`` per matmul per query head."""
    kv = cache_len + q
    pairs = q * kv - q * (q - 1) // 2
    return 4 * d["n_heads"] * d["head_dim"] * pairs


def attn_bytes_row(d: Dict, cache_len: int, q: int, kv_itemsize: int = 2,
                   act_itemsize: int = 2) -> int:
    """One layer's attention for one row: the K and V of every live
    position, read once per kv head, plus the queries read and the output
    written."""
    kv = cache_len + q
    hd = d["head_dim"]
    return (2 * d["n_kv_heads"] * hd * kv * kv_itemsize
            + 2 * q * d["n_heads"] * hd * act_itemsize)


def paged_attn(d: Dict, rows: Iterable[Row]) -> Tuple[int, int]:
    """(FLOPs, bytes) of the paged-attention kernel over a step's rows,
    all layers."""
    f = b = 0
    for cache_len, q in rows:
        f += attn_flops_row(d, cache_len, q)
        b += attn_bytes_row(d, cache_len, q)
    return d["n_layers"] * f, d["n_layers"] * b


def matmul_params(d: Dict) -> int:
    """Weights of the per-token projections, all layers, without the
    embedding table and the LM head."""
    D, hq, hkv, hd = d["d_model"], d["n_heads"], d["n_kv_heads"], d["head_dim"]
    attn = D * (hq + 2 * hkv) * hd + hq * hd * D
    mlp = (3 if d["mlp"] == "swiglu" else 2) * D * d["d_ff"]
    return d["n_layers"] * (attn + mlp)


def model_flops(d: Dict, rows: Iterable[Row]) -> int:
    """Model FLOPs of a step: 2 per weight per real token, attention over
    the causal pairs, and the LM head once per row (the megastep unembeds
    only the position it samples from)."""
    per_tok = 2 * matmul_params(d)
    head = 2 * d["d_model"] * d["vocab_size"]
    total = 0
    for cache_len, q in rows:
        total += (q * per_tok + head
                  + d["n_layers"] * attn_flops_row(d, cache_len, q))
    return total
