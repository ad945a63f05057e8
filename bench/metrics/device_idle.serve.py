"""Share of the time the engine had work in which no operation ran on the
device: the traced window less the harness's waits for the next arrival."""


def read(res, dims, peaks):
    r = res.reduced
    if r is None:
        return None
    busy_window = r.window_s - r.span_s.get("bench.wait_arrival", 0.0)
    if busy_window <= 0:
        return None
    return 100.0 * max(0.0, 1.0 - r.busy_s / busy_window)
