"""The cascade's share of its roofline: the least time its real queries
need at the chip's HBM bandwidth (``kernel_bytes/cascade.py`` over
``peaks.json``), over the device time of its executable in the trace."""


def read(run):
    if run.device is None or run.peaks is None:
        return None
    t = run.device["kernels"].get("jit_cascade_flat", 0.0)
    calls = [c for c in run.kernel_calls if c["kernel"] == "cascade"]
    if not t or not calls:
        return None
    kb = run.kernel_bytes("cascade")
    need = sum(kb.call_bytes(c) for c in calls)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / t
