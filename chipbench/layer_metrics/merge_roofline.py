"""The merge-rank kernel's share of its roofline: the least time its
real keys need at the chip's HBM bandwidth (``kernel_bytes/merge.py``
over ``peaks.json``), over the device time of its executable in the
trace.  In the cells that list it, it runs inside compactions."""


def read(run):
    if run.device is None or run.peaks is None:
        return None
    t = run.device["kernels"].get("jit_merge_ranks_ref", 0.0)
    calls = [c for c in run.kernel_calls if c["kernel"] == "merge"]
    if not t or not calls:
        return None
    kb = run.kernel_bytes("merge")
    need = sum(kb.call_bytes(c) for c in calls)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / t
