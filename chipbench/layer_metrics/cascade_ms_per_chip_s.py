"""Cascade hold of the busiest chip: for each ``device`` named by the
window's ``kernel.cascade`` spans, the union of their intervals, and
the largest union over the devices, in ms per window second.  With one
shard per chip it shows whether the chips share the cascade's hold or
one still carries it all."""


def read(run):
    by_dev = {}
    for e in run.spans:
        if e["name"] != "kernel.cascade":
            continue
        dev = (e.get("attrs") or {}).get("device")
        if dev is not None:
            by_dev.setdefault(dev, []).append(
                (max(e["t0"], run.w0), min(e["t1"], run.w1)))
    if not by_dev:
        return None
    return 1e3 * max(_union(iv) for iv in by_dev.values()) / run.window_s


def _union(intervals) -> float:
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is not None and a < end:
            a = end
        if b > a:
            total += b - a
            end = b
    return total
