"""``moe_gmm``'s share of its roofline (percent): for the grouped expert
products in the traced window (the dropless MoE's forward gate, up and
down products and the backward pass's transposed dX products), the least
time the chip could take over their device time.  The least time of a call
is the larger of its operations over the bf16 peak and its HBM bytes over
the HBM bandwidth, both from the call's HLO shapes:

* operations ``2·m·k·n`` for lhs (m, k) and out (m, n);
* bytes: lhs read once, the (E, k, n) expert weights read once, the
  output written once.

The count lives here, with the benchmark, and not in ``bench/roofline.py``
(which computes the other kernels' counts)."""

import re

import roofline

KERNEL = "moe_gmm"
ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")
BYTES = {"bf16": 2, "f16": 2, "f32": 4, "s32": 4, "s8": 1}


def shapes(hlo_text):
    """(dtype, shape) of the result and of every operand, in order."""
    head, _, args = hlo_text.partition("custom-call(")
    out = [ARRAY.search(head)] + list(ARRAY.finditer(args.split(")", 1)[0]))
    return [(m.group(1), tuple(int(n) for n in m.group(2).split(",") if n)) for m in out]


def cost(hlo_text):
    """(FLOPs, HBM bytes) of one call: its result (m, n), its lhs (m, k)
    and its (E, k, n) or (E, n, k) weights, the last two operands."""
    arrays = shapes(hlo_text)
    (out_t, (m, n)), (lhs_t, (_, k)), (rhs_t, rhs) = arrays[0], arrays[-2], arrays[-1]
    e = rhs[0]
    flops = 2.0 * m * k * n
    nbytes = BYTES[lhs_t] * m * k + BYTES[rhs_t] * e * k * n + BYTES[out_t] * m * n
    return flops, float(nbytes)


def read(run):
    t = run.trace
    if t is None or run.peak is None:
        return None
    calls, secs, ideal_s, bounds = 0, 0.0, 0.0, set()
    for name, text in t.hlo.items():
        if not name.startswith(KERNEL):
            continue
        n, s = t.op_seconds(lambda op: op.name == name)
        if n == 0:
            continue
        ideal, bound = roofline.roofline_seconds(*cost(text), run.peak)
        calls, secs, ideal_s = calls + n, secs + s, ideal_s + n * ideal
        bounds.add(bound)
    if calls == 0 or secs <= 0:
        return None
    run.notes.append(f"moe_gmm: {calls} calls read, {secs:.6f} s on device, "
                     f"{ideal_s:.6f} s at the {'/'.join(sorted(bounds))} bound")
    return 100.0 * ideal_s / secs
