"""Memtable time per request: the wall time of the window's memtable
spans, clipped to the window, over the requests submitted in it.  Two
spans count: ``lsm.get.mem``, each lookup batch's probe of the memtable
and the sealed memtables, and ``lsm.mem.merge``, each chunk of writes
merged into the memtable's sorted run.  A program without
``lsm.mem.merge`` (its memtable writes are unspanned) counts the probe
alone."""

MEM_SPANS = ("lsm.get.mem", "lsm.mem.merge")


def read(run):
    spans = [e for e in run.spans if e["name"] in MEM_SPANS]
    if not run.requests or not spans:
        return None
    s = sum(min(e["t1"], run.w1) - max(e["t0"], run.w0) for e in spans)
    return s * 1e3 / len(run.requests)
