"""Planner wait per request: over the ``plan.compile`` spans of the
window, wall time less the thread's CPU time, over the requests
submitted in it.  The planner does no I/O and waits on no device, so
this is the time the driver thread was ready to plan but did not hold
the GIL.  Spans without a ``cpu`` reading give nothing."""


def read(run):
    spans = [e for e in run.spans if e["name"] == "plan.compile"]
    if not run.requests or not spans or any(e.get("cpu") is None
                                            for e in spans):
        return None
    wait = sum(e["t1"] - e["t0"] - e["cpu"] for e in spans)
    return 1e3 * wait / len(run.requests)
