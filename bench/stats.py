"""End-to-end numbers from the client-side event log, on the host clock.

A turn record holds ``due`` (when the client meant to send it: the
previous reply plus think time, or the session's start), ``submit`` (when
``AgentRM.submit`` was called), ``begin`` (when the dispatcher began it in
the engine), ``tok_t`` (the host time at which each output token reached
the host) and ``n_out`` (tokens planned). The window is ``[w0, w1]``.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional

import numpy as np


def quantile(xs: List[float], q: float) -> Optional[float]:
    """Linear-interpolated quantile (numpy's default); None when empty."""
    return float(np.percentile(xs, 100 * q)) if xs else None


def tokens_in(turns: Iterable[Dict], w0: float, w1: float) -> int:
    return sum(1 for t in turns for x in t["tok_t"] if w0 <= x <= w1)


def ttft_samples(turns: Iterable[Dict], w0: float, w1: float) -> List[float]:
    """Every turn due in the window: due to first token; a turn with no
    token by the window's end counts with its wait so far."""
    out = []
    for t in turns:
        if not w0 <= t["due"] < w1:
            continue
        first = t["tok_t"][0] if t["tok_t"] else None
        out.append((first if first is not None and first <= w1 else w1)
                   - t["due"])
    return out


def itl_samples(turns: Iterable[Dict], w0: float, w1: float) -> List[float]:
    """Gaps between consecutive tokens of a turn that end in the window
    (clipped at its start), parks included, plus the open gap of every turn
    still decoding when the window ends."""
    out = []
    for t in turns:
        ts = [x for x in t["tok_t"] if x <= w1]
        for a, b in zip(ts, ts[1:]):
            if b >= w0:
                out.append(b - max(a, w0))
        if ts and len(ts) < t["n_out"] and not t.get("failed"):
            out.append(w1 - max(ts[-1], w0))
    return out


def queue_wait_samples(turns: Iterable[Dict], w0: float,
                       w1: float) -> List[float]:
    """Submit to first admission into the engine, for turns submitted in
    the window; a turn not yet begun counts with its wait so far."""
    out = []
    for t in turns:
        if t.get("submit") is None or not w0 <= t["submit"] < w1:
            continue
        b = t.get("begin")
        out.append((b if b is not None and b <= w1 else w1) - t["submit"])
    return out


def end_to_end(turns: List[Dict], w0: float, w1: float) -> Dict:
    ttft = ttft_samples(turns, w0, w1)
    itl = itl_samples(turns, w0, w1)
    return {
        "out_tok_s": tokens_in(turns, w0, w1) / (w1 - w0),
        "ttft_p50_s": quantile(ttft, 0.5),
        "ttft_p95_s": quantile(ttft, 0.95),
        "itl_p95_s": quantile(itl, 0.95),
        "samples": {"first_tokens": len(ttft), "gaps": len(itl),
                    "tokens": tokens_in(turns, w0, w1),
                    "turns_due": sum(1 for t in turns
                                     if w0 <= t["due"] < w1),
                    "turns_done": sum(1 for t in turns
                                      if len(t["tok_t"]) >= t["n_out"]
                                      and w0 <= t["tok_t"][-1] <= w1),
                    "parks": sum(t.get("parks_in_window", 0)
                                 for t in turns)},
    }
