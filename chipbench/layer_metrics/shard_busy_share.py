"""Share of the shard executors' time spent running plans: the
``shard.plan`` spans of the window over shards x window length."""


def read(run):
    busy = sum(min(e["t1"], run.w1) - max(e["t0"], run.w0)
               for e in run.spans if e["name"] == "shard.plan")
    if not busy:
        return None
    return 100.0 * busy / (run.shards * run.window_s)
