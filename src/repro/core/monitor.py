"""Resource Monitor (paper §IV.A component 3): global utilisation state that
feeds scheduling decisions and the PSI injection. Pure bookkeeping — cheap
enough to sit on the middleware hot path.

Since the observability PR (DESIGN.md §12) the monitor's counters live in
the unified ``MetricsRegistry``: every field of ``MonitorSnapshot`` is a
read of (or a derivation over) registry metrics, so the monitor, the
engine's stats surfaces, and every BENCH json share one store and can
never disagree. Pass the stack's shared registry in (``AgentRM`` wires its
``Observability.metrics`` through); standalone construction gets a private
one.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass
from typing import Deque, Dict, Optional

from repro.obs.metrics import LATENCY_BUCKETS_S, MetricsRegistry


@dataclass
class MonitorSnapshot:
    lanes_busy: int
    lanes_total: int
    queue_depths: Dict[int, int]
    api_utilization: float          # consumed fraction of the token bucket
    zombies_reaped: int
    recoveries: int
    context_pressure: Dict[str, float]  # agent -> window/limit
    step_time_ewma_s: float
    stragglers: int


class ResourceMonitor:
    """Tracks lanes, queues, API budget, per-agent context pressure, and a
    straggler detector (per-step EWMA + threshold, used by the training
    launcher as well). All counters are registry-backed."""

    def __init__(self, lanes_total: int = 4, straggler_factor: float = 3.0,
                 metrics: Optional[MetricsRegistry] = None):
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        m = self.metrics
        self.lanes_total = lanes_total
        self._g_lanes = m.gauge("rm.lanes_busy")
        self._c_zombies = m.counter("rm.zombies_reaped")
        self._c_recoveries = m.counter("rm.recoveries")
        self._c_stragglers = m.counter("rm.stragglers")
        self._g_api_used = m.gauge("rm.api_used")
        self._g_api_budget = m.gauge("rm.api_budget")
        self._g_api_budget.set(1.0)
        self._h_step = m.histogram("rm.step_s", LATENCY_BUCKETS_S)
        self.queue_depths: Dict[int, int] = {}
        self.context_pressure: Dict[str, float] = {}
        self._step_times: Deque[float] = deque(maxlen=64)
        self._ewma: Optional[float] = None
        self.straggler_factor = straggler_factor

    # ---- registry-backed views (kept as the historical attribute API)
    @property
    def lanes_busy(self) -> int:
        return int(self._g_lanes.value)

    @property
    def zombies_reaped(self) -> int:
        return int(self._c_zombies.value)

    @property
    def recoveries(self) -> int:
        return int(self._c_recoveries.value)

    @property
    def stragglers(self) -> int:
        return int(self._c_stragglers.value)

    @property
    def api_used(self) -> float:
        return self._g_api_used.value

    @property
    def api_budget(self) -> float:
        return self._g_api_budget.value

    # --- scheduler feed ---
    def on_lane(self, busy_delta: int):
        self._g_lanes.set(max(0, self.lanes_busy + busy_delta))

    def on_queue_depth(self, level: int, depth: int):
        self.queue_depths[level] = depth
        self.metrics.gauge(f"rm.queue_depth.q{level}").set(depth)

    def on_api(self, used: float, budget: float):
        self._g_api_used.set(used)
        self._g_api_budget.set(max(budget, 1e-9))

    def on_reap(self, recovered: bool):
        (self._c_recoveries if recovered else self._c_zombies).inc()

    # --- CLM feed ---
    def on_context(self, agent_id: str, window_tokens: int, limit: int):
        frac = window_tokens / max(limit, 1)
        self.context_pressure[agent_id] = frac

    # --- straggler detection (also used by launch/train.py) ---
    def observe_step(self, seconds: float) -> bool:
        """Returns True if this step is a straggler (> factor * EWMA)."""
        is_straggler = (self._ewma is not None
                        and seconds > self.straggler_factor * self._ewma)
        if is_straggler:
            self._c_stragglers.inc()
        alpha = 0.1
        self._ewma = seconds if self._ewma is None else \
            (1 - alpha) * self._ewma + alpha * seconds
        self._step_times.append(seconds)
        self._h_step.observe(seconds)
        return is_straggler

    def snapshot(self) -> MonitorSnapshot:
        return MonitorSnapshot(
            lanes_busy=self.lanes_busy,
            lanes_total=self.lanes_total,
            queue_depths=dict(self.queue_depths),
            api_utilization=self.api_used / max(self.api_budget, 1e-9),
            zombies_reaped=self.zombies_reaped,
            recoveries=self.recoveries,
            context_pressure=dict(self.context_pressure),
            step_time_ewma_s=self._ewma or 0.0,
            stragglers=self.stragglers,
        )
