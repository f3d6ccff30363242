"""Planner time per request: the ``plan.compile`` spans of the window
over the requests submitted in it (engine/plan.py)."""


def read(run):
    ms = sum(e["t1"] - e["t0"] for e in run.spans
             if e["name"] == "plan.compile") * 1e3
    return ms / len(run.requests) if run.requests else None
