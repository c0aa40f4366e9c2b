"""Share of the traced window in which the chip ran no op, in %:
1 - (union of the device-op intervals / the window), from the trace."""


def read(run):
    tr = run.trace
    if tr is None or not tr.devices or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
