"""Share of the traced window in which no operation runs on the
device, averaged over the cell's devices, in %."""


def read(run):
    trace = run["trace"]
    if trace is None or trace["busy_s"] <= 0:
        return None
    return 100.0 * trace["idle_share"]
