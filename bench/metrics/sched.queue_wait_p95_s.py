"""95th percentile of the MLFQ queue wait, read from the program: for each
turn submitted in the traced window (a ``sched.submitted`` instant), its
first ``sched.queued`` episode (``resumed`` 0), from ``AgentRM.submit`` to
its admission; a turn not admitted by the window's end counts with its
wait so far. The population and rule of ``queue_wait_p95_s``. Nothing
without the program's spans."""


def read(ctx):
    spans = getattr(ctx, "spans", None)
    if not spans:
        return None
    lo, hi = ctx.trace_window
    first = {}
    for e in spans:
        if e["name"] == "sched.queued" and e["args"]["resumed"] == 0:
            first.setdefault(e["args"]["tid"], e)
    waits = []
    for e in spans:
        if e["name"] != "sched.submitted" or not lo <= e["ts"] < hi:
            continue
        q = first.get(e["args"]["tid"])
        end = q["ts"] + q["dur"] if q is not None else hi
        waits.append((min(end, hi) - e["ts"]) * 1e-9)
    return ctx.stats.quantile(waits, 0.95)
