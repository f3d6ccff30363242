"""Write steps the shard executors applied per request: the write-step
spans (``shard.put``, ``shard.delete``, ``shard.range_delete``) that
open in the window, over the requests submitted in it.  Each span is
one call into the tree's batched write paths on one shard, so this
counts what a WriteBatch costs in per-call host overhead."""

WRITE_STEPS = ("shard.put", "shard.delete", "shard.range_delete")


def read(run):
    if not run.requests:
        return None
    n = sum(1 for e in run.spans if e["name"] in WRITE_STEPS
            and run.w0 <= e["t0"] < run.w1)
    return n / len(run.requests)
