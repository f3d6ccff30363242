"""Share of the window's lookups that the device cascade answered:
``cascade_queries`` over the lookups issued, as exact counts.  Below
100% means the registry declined a pack or a gate refused a batch."""


def read(run):
    issued = sum(r.n_lookups for r in run.requests)
    if not issued:
        return None
    served = run.counters1["cascade_queries"] - \
        run.counters0["cascade_queries"]
    return 100.0 * served / issued
