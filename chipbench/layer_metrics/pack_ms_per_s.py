"""Registry time per window second: the union, per thread, of the
``registry.pack`` and ``registry.upload_*`` spans of the window (a pack
holds its uploads), in ms per second of window."""


def read(run):
    by_thread = {}
    for e in run.spans:
        if e["name"].startswith("registry."):
            by_thread.setdefault(e["tid"], []).append(
                (max(e["t0"], run.w0), min(e["t1"], run.w1)))
    total = 0.0
    for iv in by_thread.values():
        end = None
        for a, b in sorted(iv):
            if end is not None and a < end:
                a = end
            if b > a:
                total += b - a
                end = b
    return 1e3 * total / run.window_s
