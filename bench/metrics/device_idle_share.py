"""Share of the traced window in which no operation ran on the device:
one minus the union of device-op intervals over the window (percent)."""


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0 or not t.ops:
        return None
    return 100.0 * (1.0 - t.busy_s() / t.window_s)
