"""Share of the traced training window in which no operation ran on the
device."""


def read(res, dims, peaks):
    r = res.reduced
    if r is None or not r.window_s:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
