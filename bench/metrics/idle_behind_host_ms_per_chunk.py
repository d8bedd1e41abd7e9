"""Device-idle milliseconds per chunk during which the host was building,
dispatching or flushing a chunk: the window's gaps between device 0's busy
runs (the device had run out of work; ``hostspans``), intersected with the
union of the program's ``fl.build.<phase>``, ``fl.dispatch`` and
``fl.flush`` spans, over the chunks the trace holds.

Idle time under ``fl.wait`` (the host blocked on the chunk's outputs),
under the enclosing ``fl.build`` alone (at pipeline depth 2 it spans the
running chunk), under no ``fl.`` span at all (the Python tracer's ``$``
frames and the runtime's own events included), or between the ops of one
busy run is not counted.  A trace with no device op, no such span or no
dispatch (a program without the spans) reads nothing."""

import hostspans
from devtrace import merge

BEHIND = ("fl.build.", "fl.dispatch", "fl.flush")


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0 or not t.ops:
        return None
    n = hostspans.chunks(t)
    host = merge([(s, e) for name, s, e in t.host if name.startswith(BEHIND)])
    if not n or not host:
        return None
    return hostspans.overlap_ns(hostspans.gaps(t), host) / 1e6 / n
