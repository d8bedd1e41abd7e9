"""Device milliseconds per round of the operations the TPU profiler puts in
a convolution category (the local-SGD forward and backward convolutions,
and the eval pass's forward ones), over the traced window."""


def read(run):
    t = run.trace
    if t is None or run.round_s <= 0 or t.window_s <= 0:
        return None
    n, secs = t.op_seconds(lambda op: "convolution" in op.category.lower())
    if n == 0:
        return None
    rounds = t.window_s / run.round_s
    return 1e3 * secs / rounds
