"""Device milliseconds per round of MLA's causal flash-attention kernels
(``mla_attention_fwd``, ``mla_attention_dq`` and ``mla_attention_dkv``:
forward and backward, eval's forward included), over the traced window.
A program without the kernels reads nothing."""

KERNEL = "mla_attention"


def read(run):
    t = run.trace
    if t is None or run.round_s <= 0 or t.window_s <= 0:
        return None
    n, secs = t.op_seconds(lambda op: op.name.split(".")[0].startswith(KERNEL))
    if n == 0:
        return None
    return 1e3 * secs / (t.window_s / run.round_s)
