"""Device milliseconds per round of the ``moe_gmm`` grouped expert
products (forward and backward, eval's forward ones included), over the
traced window."""

KERNEL = "moe_gmm"


def read(run):
    t = run.trace
    if t is None or run.round_s <= 0 or t.window_s <= 0:
        return None
    n, secs = t.op_seconds(lambda op: op.name.split(".")[0] == KERNEL)
    if n == 0:
        return None
    return 1e3 * secs / (t.window_s / run.round_s)
