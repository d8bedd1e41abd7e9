"""Host milliseconds per chunk spent building the chunk's inputs: the
program's ``fl.build.<phase>`` spans (candidates, configs, schedule,
variants, place), clipped to the traced window, less each phase's wait on
the device, over the chunks the trace holds (``hostspans``).

The wait is left out because it is the device's time, not the build's: a
phase whose eager device ops queue behind the running chunk holds the host
until that chunk ends (on one v5e, ``fl.build.variants`` of the EMNIST
cell waits ~12.5 s of its 12.7 s so).  It reads the phases and not the
enclosing ``fl.build`` span: the trace is started and stopped from inside
``propose_candidates``, which runs in ``fl.build.candidates``, so the
profiler keeps neither traced chunk's ``fl.build``.  A trace with no
device op, build phase or dispatch (a program without the spans) reads
nothing."""

import hostspans

PHASE = "fl.build."


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0 or not t.ops:
        return None
    n = hostspans.chunks(t)
    phases = hostspans.clip(
        [(s, e) for name, s, e in t.host if name.startswith(PHASE)], t.window)
    if not n or not phases:
        return None
    busy = hostspans.runs(t)
    ns = sum(e - s - hostspans.wait_ns(s, e, busy) for s, e in phases)
    return ns / 1e6 / n
