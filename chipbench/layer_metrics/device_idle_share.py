"""Share of the traced window in which no XLA op ran on the device,
averaged over the cell's chips: 1 - busy / window."""


def read(run):
    if run.device is None or run.device["busy_s"] is None:
        return None
    return 100.0 * (1.0 - run.device["busy_s"] / run.device["window_s"])
