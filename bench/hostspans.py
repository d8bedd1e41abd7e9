"""What the program's host spans say about a traced window.

The scan driver marks each host phase of its chunk loop with a profiler
``TraceAnnotation`` on the host's python line: ``fl.build`` and its
``fl.build.<phase>`` steps, ``fl.dispatch``, ``fl.wait`` and ``fl.flush``.
The readers of the host layers share three facts drawn from those spans
and device 0's ops:

- the chunks the trace holds: its ``fl.dispatch`` spans.  The harness
  starts the trace in one chunk's build and stops it in a later one's,
  so the trace holds one dispatch per traced chunk, and the count does not
  depend on the host clock (a traced run's ``round_s`` also holds the
  trace's export);
- the device's busy runs: its ops merged across gaps under ``RUN_GAP_NS``.
  A compiled program's own ops follow one another within microseconds (on
  one v5e the EMNIST chunk idles under 1 ms in all over its 12.6 s), so a
  gap of a millisecond or more is the device waiting for the host;
- a phase's wait on the device: where a busy run that was in progress when
  a host phase began ends inside that phase, the phase was held by it
  from its start to the run's end.  The build's eager device ops queue
  behind the running chunk, and the host blocks on them until it ends.
"""
from typing import List, Tuple

from devtrace import merge

Interval = Tuple[float, float]
RUN_GAP_NS = 1e6


def chunks(t) -> int:
    return sum(1 for name, _, _ in t.host if name == "fl.dispatch")


def runs(t) -> List[Interval]:
    out: List[List[float]] = []
    for s, e in merge([(o.start_ns, o.start_ns + o.dur_ns) for o in t.ops[min(t.ops)]]):
        if out and s - out[-1][1] < RUN_GAP_NS:
            out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, window) -> List[Interval]:
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def wait_ns(s: float, e: float, busy: List[Interval]) -> float:
    """Nanoseconds of the phase ``[s, e]`` held by a busy run that was in
    progress when it began and ended inside it."""
    return sum(b - s for a, b in busy if a < s < b <= e)


def gaps(t) -> List[Interval]:
    """The window's intervals between device 0's busy runs."""
    lo, hi = t.window
    edges = [lo] + [x for iv in runs(t) for x in iv] + [hi]
    return clip(zip(edges[0::2], edges[1::2]), t.window)


def overlap_ns(a: List[Interval], b: List[Interval]) -> float:
    """Summed overlap of two sorted lists of disjoint intervals."""
    ns = 0.0
    i = j = 0
    while i < len(a) and j < len(b):
        ns += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return ns
