"""95th percentile, over every lookup-only request (MultiGet) of the
window, of the time from submit until its results were collected."""

import numpy as np


def read(run):
    lat = [r.t1 - r.t0 for r in run.requests if r.n_lookups == r.n_ops]
    return float(np.percentile(lat, 95) * 1e3) if lat else None
