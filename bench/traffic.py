"""The one traffic generator: a mix file of parameters in, a session plan out.

A mix (``bench/traffic/<name>.json``) describes closed-loop agent sessions:
how many, how long their system prompt is, how much history each brings,
the shape of a turn (tokens sent, tokens generated), the think time
between a reply and the next turn, and the history length at which a
session ends and a fresh one starts from the system prompt. With
``"tenants": N`` there are N system prompts, one per tenant, and session
slot ``i`` belongs to tenant ``i mod N``; without it every session shares
one.

Every length and think time comes from the mix's own ``size_seed``, so the
work is the same for every run seed: the run seed only permutes which
session slot gets which schedule, and draws the bytes of every prompt. A
turn's byte count is its token count, since the served path tokenises one
token per byte.

The arrival process and the heavy-tailed length draws follow the
load generator in ``benchmarks/workload.py`` (Pareto bodies, exponential
think times), with the cells' parameters.
"""
from __future__ import annotations

import dataclasses
import math
import random
from typing import Dict, List

# printable ASCII, so a prompt's bytes are its tokens one for one
ALPHABET = bytes(range(33, 127))


def draw(rng: random.Random, dist: Dict) -> float:
    """One draw from a length or time distribution given as data."""
    kind = dist["dist"]
    if kind == "fixed":
        x = float(dist["value"])
    elif kind == "uniform":
        x = rng.uniform(dist["lo"], dist["hi"])
    elif kind == "pareto":
        # Pareto(alpha) scaled to its minimum: mostly short, a heavy tail
        x = dist["min"] * rng.paretovariate(dist["alpha"])
    elif kind == "lognormal":
        x = rng.lognormvariate(math.log(dist["median"]), dist["sigma"])
    elif kind == "exponential":
        x = rng.expovariate(1.0 / dist["mean"])
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "lo" in dist and kind != "uniform":
        x = max(x, dist["lo"])
    if "hi" in dist and kind != "uniform":
        x = min(x, dist["hi"])
    if "cap" in dist:
        x = min(x, dist["cap"])
    return x


@dataclasses.dataclass
class Turn:
    n_in: int          # tokens the client sends
    n_out: int         # tokens the turn generates
    think_s: float     # client think time before the turn is sent


@dataclasses.dataclass
class Slot:
    """One client: a schedule of turns, restarted as a fresh session when
    its history would pass the mix's reset length."""
    history: int                 # tokens after the system prompt, turn 0
    turns: List[Turn]


def plan_slots(mix: Dict) -> List[Slot]:
    """The mix's schedules, from its ``size_seed`` alone."""
    rng = random.Random(mix["size_seed"])
    sys_tokens = mix["system_prompt_tokens"]
    limit = mix["reset_after_tokens"]
    slots = []
    for _ in range(mix["sessions"]):
        hist = int(draw(rng, mix["history_tokens"])) \
            if "history_tokens" in mix else 0
        turns = []
        for _ in range(mix["turns_per_session"]):
            n_out = int(draw(rng, mix["output_tokens"]))
            n_in = int(draw(rng, mix["input_tokens"]))
            # a turn must fit a fresh session: system prompt, the turn, and
            # the previous reply's last token, which the turn writes first
            n_in = max(1, min(n_in, limit - sys_tokens - n_out - 1))
            turns.append(Turn(n_in, n_out, draw(rng, mix["think_s"])))
        slots.append(Slot(hist, turns))
    return slots


def permuted(slots: List[Slot], seed: int) -> List[Slot]:
    """The same schedules in a seed-chosen order of slots."""
    order = list(range(len(slots)))
    random.Random(seed).shuffle(order)
    return [slots[i] for i in order]


def tenants(mix: Dict) -> int:
    return int(mix.get("tenants", 1))


def tenant_of(slot: int, mix: Dict) -> int:
    """The tenant of session slot ``slot``: round-robin over the slots."""
    return slot % tenants(mix)


def system_prompts(rng: random.Random, mix: Dict) -> List[str]:
    """One system prompt per tenant, drawn in turn from ``rng``; the first
    is the one prompt of a mix without tenants."""
    return [text(rng, mix["system_prompt_tokens"])
            for _ in range(tenants(mix))]


def text(rng: random.Random, n: int) -> str:
    """``n`` printable bytes (= ``n`` byte tokens)."""
    return bytes(rng.choices(ALPHABET, k=n)).decode("ascii")
