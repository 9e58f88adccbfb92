"""Median wait of a parked turn to be admitted again: the ``sched.queued``
episodes with ``resumed`` 1 that end in the traced window, and those still
open at its end (a park, ``sched.preempted`` or ``sched.kv_degraded``,
with no later episode of its turn), with their wait so far. Nothing
without the program's spans."""

PARKS = {"sched.preempted": "tid", "sched.kv_degraded": "victim_tid"}


def read(ctx):
    spans = getattr(ctx, "spans", None)
    if not spans:
        return None
    lo, hi = ctx.trace_window
    resumed = [e for e in spans
               if e["name"] == "sched.queued" and e["args"]["resumed"] == 1]
    eps = [(e["ts"], e["ts"] + e["dur"]) for e in resumed]
    for e in spans:
        key = PARKS.get(e["name"])
        if key is None:
            continue
        tid = e["args"][key]
        if not any(q["args"]["tid"] == tid and q["ts"] >= e["ts"]
                   for q in resumed):
            eps.append((e["ts"], None))           # still waiting
    waits = []
    for s, t in eps:
        if t is not None and lo <= t <= hi:
            waits.append((t - s) * 1e-9)
        elif (t is None or t > hi) and s <= hi:
            waits.append((hi - s) * 1e-9)
    return ctx.stats.quantile(waits, 0.5)
