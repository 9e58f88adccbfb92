"""The plain reference of a served model, and the comparison that decides
``correct``.

The reference itself is the cell's architecture module
(``archs/<name>.py``): its ``forward_chunk`` in the configuration's
stated precision, and with ``control`` the same forward in the nearest
precision below it. It imports nothing of the served program and is given
only the weights the benchmark made.

A sequence is run in blocks of ``CHUNK`` query rows against the cache
the module's ``cache_shapes`` describes (a pytree, allocated here in the
configuration's dtype), so one compiled program serves every length and
the whole context never sits in one attention matrix.
"""
from __future__ import annotations

import functools
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


@functools.lru_cache(maxsize=None)
def _compiled(arch, key, control: bool):
    """The jitted forward of one block, keyed by the architecture module,
    the sizes and the precision."""
    jax, _ = _jnp()
    d = dict(key)
    return jax.jit(functools.partial(arch.forward_chunk, d, control),
                   donate_argnums=(1,))


def _run(arch, d: Dict, control: bool, params, toks: np.ndarray, want,
         cache_len: int):
    """Max logit, logit of ``want`` (the token to score at each position;
    by default the next one in ``toks``) and argmax at every position of
    ``toks`` but the last."""
    jax, jnp = _jnp()
    fn = _compiled(arch, tuple(sorted(d.items())), control)
    n = len(toks)
    dtype = jnp.dtype(d["dtype"])
    cache = jax.tree.map(lambda s: jnp.zeros(s, dtype),
                         arch.cache_shapes(d, cache_len),
                         is_leaf=lambda x: isinstance(x, tuple))
    pick = np.zeros(cache_len, np.int32)
    pick[:n - 1] = toks[1:] if want is None else want
    pad = np.zeros(cache_len, np.int32)
    pad[:n] = toks
    outs = []
    for start in range(0, n - 1, CHUNK):
        cache, *out = fn(params, cache,
                         jnp.asarray(pad[start:start + CHUNK]),
                         jnp.asarray(pick[start:start + CHUNK]),
                         jnp.int32(start))
        outs.append(out)
    return tuple(np.concatenate([np.asarray(o[i]) for o in outs])[:n - 1]
                 for i in range(3))


def compare(arch, d: Dict, params, sequences: Sequence, max_len: int,
            control: bool = False) -> Dict:
    """Check served tokens against the reference.

    ``sequences``: (tokens, served) pairs; ``tokens`` is a session's whole
    token stream (prompts and served tokens in the order the engine wrote
    them) and ``served`` a boolean mask over it, true where the engine
    produced the token. Returns the widest gap by which a served token's
    reference logit lies below the reference's best, and the share of
    served tokens that are the reference's argmax.

    ``arch`` is the cell's architecture module and ``d`` its ``dims``.
    With ``control`` the module's lower-precision control takes the
    program's place: at each served position the token it puts first is
    scored instead of the served one, and its widest gap is returned as
    ``max_logit_gap``; the served tokens' own gap is returned as
    ``program_max_logit_gap``."""
    cache_len = -(-(max_len + 1) // CHUNK) * CHUNK
    gap = ctl_gap = 0.0
    n_served = n_agree = 0
    for toks, served in sequences:
        toks = np.asarray(toks, np.int32)
        at = np.asarray(served, bool)[1:]           # predicted from pos - 1
        if not at.any():
            continue
        mx, picked, arg = _run(arch, d, False, params, toks, None,
                               cache_len)
        gap = max(gap, float((mx - picked)[at].max()))
        n_served += int(at.sum())
        n_agree += int((arg == toks[1:])[at].sum())
        if control:
            _, _, carg = _run(arch, d, True, params, toks, None, cache_len)
            _, ref_at_ctl, _ = _run(arch, d, False, params, toks, carg,
                                    cache_len)
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
