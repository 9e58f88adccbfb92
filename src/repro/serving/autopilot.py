"""SLO-feedback overload autopilot (DESIGN.md §16).

Every protective knob in the serving stack used to be a static constant:
the megastep ``token_budget``, the admission rate, the degrade ladder.
Under sustained arrivals beyond KV/compute capacity that means TTFT
grows without bound while the stack sheds nothing — exactly the
unresponsiveness cascade the paper's OS-style resource management is
supposed to prevent. ``SLOAutopilot`` closes the loop: each dispatcher
pass it reads *windowed* ITL/TTFT p95 and admission-queue depth from the
shared ``MetricsRegistry`` and walks a brownout ladder with hysteresis:

  rung 0  healthy       — full token budget, everything admitted
  rung 1  budget shrink — retune the megastep ``token_budget`` LIVE,
                          one pre-traced pow2 bucket at a time, toward
                          the decode-first floor (``max_batch``). Zero
                          recompiles by construction: the bucket set is
                          fixed and pre-traced, only the budget moves
                          between its members (``set_token_budget``).
                          Signal-directed: the cut applies only while a
                          LATENCY SLO (TTFT/ITL) is breached — smaller
                          steps bound step latency, but they cannot
                          drain a deep queue, they just lower capacity
                          exactly when demand exceeds it. A queue-only
                          breach climbs the ladder with the budget at
                          full and lets shed own the backlog.
  rung 2  hibernate     — park-and-swap idle / MLFQ-lowest sessions so
                          their KV pages go cold, freeing device blocks
                          for the turns actually decoding.
  rung 3  rebalance     — fleet-level ``rebalance_for_admission``: re-
                          home the head-of-queue waiter or migrate an
                          idle victim to an engine with headroom.
  rung 4  shed          — refuse NEW admissions with a typed
                          ``BackpressureError`` carrying a finite
                          ``retry_after_s`` from the admission bucket's
                          ``next_slot``. Nothing already admitted or
                          parked is touched, so the MLFQ starvation
                          boost keeps its guarantee.

Escalation requires ``breach_passes`` consecutive breached assessments;
recovery requires ``clear_passes`` consecutive healthy ones *below* a
clear fraction of the SLO (classic dual-threshold hysteresis, so the
ladder cannot flap on a noisy p95). Recovery walks the same ladder
rung-by-rung in reverse — shedding lifts first, the budget restores
last-step-first — and hibernated sessions wake lazily on their next
turn, so nothing thunders back in.

The autopilot is policy only: the middleware owns the mechanisms and
hands them over as callbacks at ``bind`` time (hibernate a victim,
rebalance the head waiter), and checks ``shedding`` at submit time.
Shed-rung SLO breaches are also fed to the AIMD admission controller
(``on_slo_breach``) which grows a client-facing shed backoff — so the
``retry_after_s`` clients see stretches while the ladder is deployed —
WITHOUT cutting the internal admission multiplier: throttling our own
queue->engine drain while our engine is the bottleneck would be a
congestion-collapse feedback loop (see ``AIMDController``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, List, Optional

__all__ = ["AutopilotConfig", "SLOAutopilot"]


@dataclass
class AutopilotConfig:
    """SLO targets + controller dynamics. Defaults suit the CI-box CPU
    smoke models; real deployments set the two SLOs from their latency
    contract and leave the dynamics alone."""

    slo_ttft_p95_s: float = 2.0     # windowed TTFT p95 target
    slo_itl_p95_s: float = 0.5      # windowed ITL p95 target
    window_s: float = 5.0           # control-signal recency window
    min_samples: int = 6            # per-signal floor before it may vote
    queue_high: Optional[int] = None  # breach above this depth (None:
    #                                   the middleware fills in 8*lanes)
    clear_frac: float = 0.8         # healthy means p95 < clear_frac*SLO
    breach_passes: int = 3          # consecutive breaches to escalate
    clear_passes: int = 6           # consecutive healthy to relax
    check_interval_s: float = 0.2   # min seconds between assessments
    min_retry_after_s: float = 0.05  # shed retry_after floor
    max_retry_after_s: float = 30.0  # ... and ceiling (always finite)
    # at the shed rung, refuse a NEW admission only while the queue
    # already holds at least this many turns (None: queue_high // 2,
    # floored at 2). The valve sheds the EXCESS, not the trickle that
    # keeps the engine fed: a binary shed-everything rung duty-cycles
    # between "reject all" and "drained to idle", and the idle half of
    # that cycle is capacity thrown away while clients are retrying
    shed_queue_floor: Optional[int] = None


def _live_engines(backend) -> List[object]:
    """Engines behind a backend, duck-typed: a fleet exposes ``members``
    (dead ones excluded), adapters expose ``engine``, chaos wrappers
    expose ``inner``. getattr-with-default swallows AttributeErrors from
    delegating properties, so any shape degrades to an empty list."""
    members = getattr(backend, "members", None)
    if members is not None:
        out = []
        for m in members:
            if not getattr(m, "alive", True):
                continue
            eng = getattr(getattr(m, "backend", None), "engine", None)
            if eng is not None:
                out.append(eng)
        return out
    eng = getattr(backend, "engine", None)
    if eng is not None:
        return [eng]
    inner = getattr(backend, "inner", None)
    return _live_engines(inner) if inner is not None else []


class SLOAutopilot:
    """The closed-loop controller. One instance per AgentRM; the
    dispatcher calls ``on_pass`` once per scheduling pass under the
    middleware lock, and ``submit`` consults ``shedding``."""

    def __init__(self, cfg: Optional[AutopilotConfig] = None, obs=None):
        self.cfg = cfg or AutopilotConfig()
        self.obs = obs
        self._backend = None
        self._hibernate: Optional[Callable[[], bool]] = None
        self._rebalance: Optional[Callable[[], bool]] = None
        self._aimd = None
        # severity is the ladder position: 0 healthy, 1..budget_steps the
        # budget band (rung 1), then hibernate / rebalance / shed
        self.severity = 0
        self._budget_steps = 0
        self._breach_streak = 0
        self._clear_streak = 0
        # signal-directed budget lever: the token-budget cut fires only
        # while a LATENCY SLO (TTFT/ITL of admitted turns) is breached.
        # A queue-only breach keeps the budget at full — smaller steps
        # cannot drain a deep queue, they just lower capacity exactly
        # when demand exceeds it; admission control (shed) owns the queue
        self.latency_breached = False
        self._lat_clear_streak = 0
        self._last_check = None
        # last observed signals, for step_stats-style introspection
        self.last_signals: dict = {}

    # ------------------------------------------------------------ wiring
    def bind(self, backend, *, hibernate=None, rebalance=None, aimd=None,
             obs=None):
        """Attach mechanisms: the backend (for engine discovery), the
        middleware's hibernate-a-victim / rebalance-head-waiter
        callbacks, and the AIMD controller breaches feed."""
        self._backend = backend
        self._hibernate = hibernate
        self._rebalance = rebalance
        self._aimd = aimd
        if obs is not None:
            self.obs = obs
        rungs = [len(e.budget_rungs())
                 for e in _live_engines(backend)
                 if hasattr(e, "budget_rungs")]
        self._budget_steps = max(rungs) - 1 if rungs else 0
        m = self._metrics()
        if m is not None:
            m.gauge("autopilot.rung").set(0)
            m.gauge("autopilot.severity").set(0)

    def _metrics(self):
        return getattr(self.obs, "metrics", None)

    # ------------------------------------------------------------ state
    @property
    def max_severity(self) -> int:
        return self._budget_steps + 3

    @property
    def rung(self) -> int:
        if self.severity == 0:
            return 0
        if self.severity <= self._budget_steps:
            return 1
        return min(4, 1 + self.severity - self._budget_steps)

    @property
    def shedding(self) -> bool:
        return self.rung >= 4

    def should_shed(self, queue_depth: int) -> bool:
        """Shed this admission? Only at the shed rung, and only while the
        queue already holds enough turns to keep the engine fed — rung 4
        caps the backlog rather than closing the valve outright, so the
        engine drains at capacity while the excess gets typed rejections."""
        if not self.shedding:
            return False
        floor = self.cfg.shed_queue_floor
        if floor is None:
            qhigh = (self.cfg.queue_high
                     if self.cfg.queue_high is not None else 32)
            floor = max(2, qhigh // 2)
        return queue_depth >= floor

    # ----------------------------------------------------------- signals
    def _engine_names(self) -> List[str]:
        return [getattr(e, "name", "engine")
                for e in _live_engines(self._backend)]

    def _worst_p95(self, suffix: str, now: float) -> Optional[float]:
        """Max windowed p95 across live engines (the worst engine
        governs); None when no engine has enough recent samples."""
        m = self._metrics()
        if m is None:
            return None
        worst = None
        for name in self._engine_names():
            h = m.get(f"{name}.{suffix}")
            if h is None:
                continue
            if h.windowed_count(self.cfg.window_s, now) < self.cfg.min_samples:
                continue
            q = h.windowed_quantile(0.95, self.cfg.window_s, now)
            if q is not None and (worst is None or q > worst):
                worst = q
        return worst

    def _assess(self, now: float, queue_depth: int):
        """One dual-threshold SLO check. Returns (breached, healthy):
        signals without enough samples abstain from BOTH verdicts, so an
        idle engine neither escalates nor relaxes on stale data — except
        that an empty queue with no latency signal at all counts as
        healthy (traffic stopped: recover)."""
        cfg = self.cfg
        ttft = self._worst_p95("ttft_s", now)
        itl = self._worst_p95("itl_s", now)
        qhigh = cfg.queue_high if cfg.queue_high is not None else 32
        self.last_signals = {"ttft_p95_s": ttft, "itl_p95_s": itl,
                             "queue_depth": queue_depth}
        m = self._metrics()
        if m is not None:
            if ttft is not None:
                m.gauge("autopilot.ttft_p95_s").set(ttft)
            if itl is not None:
                m.gauge("autopilot.itl_p95_s").set(itl)
            m.gauge("autopilot.queue_depth").set(queue_depth)
        lat_over = ((ttft is not None and ttft > cfg.slo_ttft_p95_s)
                    or (itl is not None and itl > cfg.slo_itl_p95_s))
        lat_clear = ((ttft is not None or itl is not None)
                     and (ttft is None or ttft < cfg.clear_frac
                          * cfg.slo_ttft_p95_s)
                     and (itl is None or itl < cfg.clear_frac
                          * cfg.slo_itl_p95_s))
        # the budget lever tracks the latency signals alone, with its own
        # dual-threshold hysteresis: cut while TTFT/ITL are over SLO,
        # restore after clear_passes assessments below clear_frac*SLO.
        # Abstaining signals (no recent samples) neither cut nor restore
        if lat_over:
            self._lat_clear_streak = 0
            if not self.latency_breached:
                self.latency_breached = True
                self._apply_budgets()
        elif lat_clear and self.latency_breached:
            self._lat_clear_streak += 1
            if self._lat_clear_streak >= cfg.clear_passes:
                self._lat_clear_streak = 0
                self.latency_breached = False
                self._apply_budgets()
        breached = queue_depth > qhigh or lat_over
        healthy = (queue_depth <= max(1, qhigh // 2)
                   and (ttft is None or ttft < cfg.clear_frac
                        * cfg.slo_ttft_p95_s)
                   and (itl is None or itl < cfg.clear_frac
                        * cfg.slo_itl_p95_s))
        if ttft is None and itl is None and queue_depth > 0:
            healthy = False      # work is queued but nothing finished
        return breached, healthy

    # ----------------------------------------------------------- actions
    def _apply_budgets(self):
        """Install the current severity's token budget on every live
        paged engine: ``steps_down`` buckets below its full budget,
        floored at its own decode-first rung — but ONLY while a latency
        SLO is actually breached (a queue-only breach leaves the budget
        at full: see ``latency_breached``). Idempotent; always within
        the engine's fixed pre-traced bucket set."""
        steps_down = (min(self.severity, self._budget_steps)
                      if self.latency_breached else 0)
        for eng in _live_engines(self._backend):
            if not hasattr(eng, "budget_rungs"):
                continue
            ladder = eng.budget_rungs()
            if not ladder:
                continue
            target = ladder[max(0, len(ladder) - 1 - steps_down)]
            if target != eng.token_budget:
                eng.set_token_budget(target)

    def _publish(self):
        m = self._metrics()
        if m is not None:
            m.gauge("autopilot.rung").set(self.rung)
            m.gauge("autopilot.severity").set(self.severity)

    def _escalate(self) -> bool:
        if self.severity >= self.max_severity:
            return False
        self.severity += 1
        self._apply_budgets()
        self._publish()
        m = self._metrics()
        if m is not None:
            m.counter("autopilot.escalations").inc()
        return True

    def _relax(self) -> bool:
        if self.severity == 0:
            return False
        self.severity -= 1
        self._apply_budgets()
        self._publish()
        m = self._metrics()
        if m is not None:
            m.counter("autopilot.relaxations").inc()
        return True

    # -------------------------------------------------------- main hook
    def on_pass(self, now: float, queue_depth: int) -> Optional[str]:
        """One dispatcher-pass tick. Rate-limited to
        ``check_interval_s``; applies at most one ladder move and one
        mechanism action per assessment. Returns a short action tag for
        tracing, or None."""
        cfg = self.cfg
        if self._last_check is not None \
                and now - self._last_check < cfg.check_interval_s:
            return None
        self._last_check = now
        breached, healthy = self._assess(now, queue_depth)
        action = None
        if breached:
            self._clear_streak = 0
            self._breach_streak += 1
            if self._aimd is not None and self.shedding:
                # shed-rung breaches grow the client-facing shed backoff,
                # so retry_after_s stretches while the overload persists
                # (internal drain admission is deliberately untouched)
                self._aimd.on_slo_breach()
            if self._breach_streak >= cfg.breach_passes:
                self._breach_streak = 0
                if self._escalate():
                    action = f"escalate:rung{self.rung}"
            # while deployed at a mechanism rung, keep applying it on
            # every breached assessment (one bounded action each)
            if self.rung >= 2 and self._hibernate is not None:
                if self._hibernate():
                    action = action or "hibernate"
                    m = self._metrics()
                    if m is not None:
                        m.counter("autopilot.hibernates").inc()
            if self.rung >= 3 and self._rebalance is not None:
                if self._rebalance():
                    action = action or "rebalance"
                    m = self._metrics()
                    if m is not None:
                        m.counter("autopilot.rebalances").inc()
        elif healthy:
            self._breach_streak = 0
            self._clear_streak += 1
            if self._clear_streak >= cfg.clear_passes:
                self._clear_streak = 0
                if self._relax():
                    action = f"relax:rung{self.rung}"
        else:
            # ambiguous (between thresholds, or signals abstained):
            # hold position, decay both streaks
            self._breach_streak = max(0, self._breach_streak - 1)
            self._clear_streak = max(0, self._clear_streak - 1)
        return action

    def retry_after(self, next_slot_s: float) -> float:
        """Clamp an admission-bucket ``next_slot`` into the finite
        [min, max] retry window ``BackpressureError`` promises."""
        cfg = self.cfg
        s = next_slot_s if next_slot_s == next_slot_s else 0.0  # NaN guard
        return float(min(max(s, cfg.min_retry_after_s),
                         cfg.max_retry_after_s))

    def stats(self) -> dict:
        m = self._metrics()

        def c(name):
            cnt = m.get(name) if m is not None else None
            return int(cnt.value) if cnt is not None else 0

        return {"rung": self.rung, "severity": self.severity,
                "max_severity": self.max_severity,
                "budget_steps": self._budget_steps,
                "latency_breached": self.latency_breached,
                "escalations": c("autopilot.escalations"),
                "relaxations": c("autopilot.relaxations"),
                "hibernates": c("autopilot.hibernates"),
                "rebalances": c("autopilot.rebalances"),
                **{k: v for k, v in self.last_signals.items()}}
