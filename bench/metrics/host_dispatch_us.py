"""Mean host time of one eager ``repro.reduce`` call, from the call to
its return (before blocking on the result): the front door's dispatch
cost, on the host clock.  Read over the calls of the traced run's window
that ran while the profiler was off, so tracing does not inflate it."""


def read(run):
    d = run.facts.get("dispatch_s_untraced") or []
    return 1e6 * sum(d) / len(d) if d else None
