"""95th percentile, over every write-only request (WriteBatch of updates
and range deletes) of the window, of the time from submit until it was
acknowledged."""

import numpy as np


def read(run):
    lat = [r.t1 - r.t0 for r in run.requests if r.n_lookups == 0]
    return float(np.percentile(lat, 95) * 1e3) if lat else None
