"""``cross_gram``'s share of its roofline (percent): for the calls in the
traced window, the least time the chip could take (the larger of the
operations over the bf16 peak and the HBM bytes over the HBM bandwidth,
from the shapes, ``bench/roofline.py``) over their device time.  The
ingest calls it twice a round, U (P, D) against the V and the A map
(M, D).

XLA may place an operand in on-chip memory (``S(1)`` in its layout)
before the kernel runs; such an operand costs the kernel no HBM traffic.
Calls whose (M, D) map sits on chip are left out: their time no longer
depends on HBM, and this roofline does not describe them."""

import re

import roofline

KERNEL = "cross_gram"
ARRAY = re.compile(r"\w+\[([\d,]*)\]\{([^}]*)\}")


def placement(hlo_text):
    """(shape, on chip) of the result and of the two operands, from the
    instruction's HLO text (the operands come first after the call)."""
    head, _, args = hlo_text.partition("custom-call(")
    arrays = [ARRAY.search(head)] + list(ARRAY.finditer(args))[:2]
    return [(tuple(int(n) for n in a.group(1).split(",")), "S(1)" in a.group(2))
            for a in arrays]


def read(run):
    t = run.trace
    if t is None or run.peak is None:
        return None
    calls, secs, ideal_s, bounds = 0, 0.0, 0.0, set()
    for name, text in t.hlo.items():
        if not name.startswith(KERNEL):
            continue
        (out, out_vmem), (u, u_vmem), (v, v_vmem) = placement(text)
        if v_vmem:
            run.notes.append(f"{name}: its (M, D) operand is on chip; left out")
            continue
        n, s = t.op_seconds(lambda op: op.name == name)
        (k, d), m = u, v[0]
        flops, nbytes = roofline.cross_gram_cost(k, m, d, in_hbm=(not u_vmem, True, not out_vmem))
        ideal, bound = roofline.roofline_seconds(flops, nbytes, run.peak)
        calls, secs, ideal_s = calls + n, secs + s, ideal_s + n * ideal
        bounds.add(bound)
    if calls == 0 or secs <= 0:
        return None
    run.notes.append(f"cross_gram: {calls} calls read, {secs:.6f} s on device, "
                     f"{ideal_s:.6f} s at the {'/'.join(sorted(bounds))} bound")
    return 100.0 * ideal_s / secs
