"""Reading a profiler trace: device operations, the traced window, busy
time, idle gaps and what the host was doing in them.

    python3 bench/devtrace.py <dir>      # print a summary of a recorded trace

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads its events.  Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per executed
HLO operation, Pallas kernels included (a kernel is named after the
jitted function that calls ``pallas_call``, e.g. ``cross_gram.3``).  An
event's name is the instruction's HLO text; its short name and its
``hlo_category`` sit in the plane's event metadata, which ``ProfileData``
does not expose, so a few lines below read them straight from the
protobuf.  Loops and calls (``while``, ``conditional``, ``call``) hold
other ops and are left out of per-op sums.  The window is the interval
between the end of ``start_trace`` and the start of ``stop_trace`` on the
host's python thread.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import math
import os
import sys
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    name: str                 # short HLO name, e.g. "fusion.423", "cross_gram.1"
    start_ns: float
    dur_ns: float
    category: str             # the profiler's hlo_category

    @property
    def container(self) -> bool:
        return self.category in CONTAINERS or self.name.split(".")[0] in CONTAINERS


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]                  # ns
    ops: Dict[int, List[Op]]                     # device id -> ops in window
    host: List[Tuple[str, float, float]]         # (name, start, end) python thread
    # custom calls' HLO text (operands included), and the seconds of each
    # async copy ("copy-start.N") per device, summed over the window
    hlo: Dict[str, str] = dataclasses.field(default_factory=dict)
    copies: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the devices."""
        total = [union_ns([(o.start_ns, o.start_ns + o.dur_ns) for o in ops], self.window)
                 for ops in self.ops.values()]
        return sum(total) / len(total) / 1e9 if total else 0.0

    def op_seconds(self, match) -> Tuple[int, float]:
        """(count, device seconds per device) of the ops ``match`` accepts."""
        n, dur = 0, 0.0
        for ops in self.ops.values():
            for o in ops:
                if not o.container and match(o):
                    n += 1
                    dur += o.dur_ns
        k = max(len(self.ops), 1)
        return n // k, dur / k / 1e9

    def top_ops(self, n: int = 10) -> List[List]:
        tot: Dict[str, float] = collections.Counter()
        for ops in self.ops.values():
            for o in ops:
                if not o.container:
                    tot[o.name] += o.dur_ns / 1e9
        k = max(len(self.ops), 1)
        return [[name, s / k] for name, s in sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The longest device-idle intervals in the window, each named by the
        host event that overlaps it most (``idle`` where none does)."""
        out = []
        for ops in self.ops.values():
            busy = merge([(o.start_ns, o.start_ns + o.dur_ns) for o in ops])
            edges = [self.window[0]] + [x for iv in busy for x in iv] + [self.window[1]]
            for a, b in zip(edges[0::2], edges[1::2]):
                a, b = max(a, self.window[0]), min(b, self.window[1])
                if b - a > 1e3:                     # gaps over a microsecond
                    out.append((b - a, a, b))
            break                                   # device 0 names the gaps
        out.sort(reverse=True)
        named = []
        for dur, a, b in out[:n]:
            # the innermost host span that covers most of the gap
            best, span = "idle", math.inf
            for name, s, e in self.host:
                if min(b, e) - max(a, s) >= 0.5 * (b - a) and e - s < span:
                    best, span = name, e - s
            named.append([best, dur / 1e9])
        return named


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def union_ns(intervals: List[Tuple[float, float]], window: Tuple[float, float]) -> float:
    lo, hi = window
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merge(intervals))


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b: bytes, i: int = 0, end: Optional[int] = None):
    """(field, value) of one protobuf message; length-delimited values come
    as (start, end) offsets, so nested messages are read without copies."""
    end = len(b) if end is None else end
    while i < end:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = None, i + 8
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = (i, i + n), i + n
        elif wire == 5:
            v, i = None, i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, v


def op_categories(raw: bytes) -> Dict[str, str]:
    """HLO text -> hlo_category, from the device planes' event
    metadata (XSpace.planes=1; XPlane.name=2, event_metadata=4,
    stat_metadata=5; XEventMetadata.name=2, display_name=4, stats=5;
    XStat.metadata_id=1, str_value=5; XStatMetadata.name=2)."""
    text = lambda span: raw[span[0]:span[1]].decode("utf-8", "replace")
    out: Dict[str, str] = {}
    for f, plane in _fields(raw):
        if f != 1:
            continue
        name, metas, stat_names = "", [], {}
        for f2, v2 in _fields(raw, *plane):
            if f2 == 2:
                name = text(v2)
            elif f2 == 4:
                metas.append(v2)
            elif f2 == 5:
                key = val = None
                for f3, v3 in _fields(raw, *v2):
                    if f3 == 1:
                        key = v3
                    elif f3 == 2:
                        val = next((text(v4) for f4, v4 in _fields(raw, *v3) if f4 == 2), None)
                stat_names[key] = val
        if not name.startswith(DEVICE_PREFIX):
            continue
        for entry in metas:
            for f3, v3 in _fields(raw, *entry):
                if f3 != 2:
                    continue
                long_name = category = ""
                for f4, v4 in _fields(raw, *v3):
                    if f4 == 2:
                        long_name = text(v4)
                    elif f4 == 5:
                        sid = sval = None
                        for f5, v5 in _fields(raw, *v4):
                            if f5 == 1:
                                sid = v5
                            elif f5 == 5:
                                sval = text(v5)
                        if stat_names.get(sid) == "hlo_category" and sval is not None:
                            category = sval
                if category:
                    out[long_name] = category
    return out


def _short(hlo_text: str) -> str:
    head = hlo_text.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def find_xplane(root: str) -> str:
    files = sorted(glob.glob(os.path.join(root, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {root}")
    return files[-1]


def load(root: str) -> Trace:
    from jax.profiler import ProfileData

    path = find_xplane(root)
    with open(path, "rb") as f:
        raw = f.read()
    category = op_categories(raw)
    pd = ProfileData.from_serialized_xspace(raw)
    host: List[Tuple[str, float, float]] = []
    start = stop = None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if not line.name.startswith("python"):
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if ev.name == "start_trace":
                    start = e
                elif ev.name == "stop_trace":
                    stop = s
                else:
                    host.append((ev.name, s, e))
    ops: Dict[int, List[Op]] = {}
    hlo: Dict[str, str] = {}
    async_ops: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        dev = int(plane.name[len(DEVICE_PREFIX):].split()[0])
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops[dev] = []
                for ev in line.events:
                    cat = category.get(ev.name, "")
                    op = Op(_short(ev.name), ev.start_ns, ev.duration_ns, cat)
                    if cat == "custom-call":
                        hlo.setdefault(op.name, ev.name)
                    ops[dev].append(op)
            elif line.name == "Async " + OPS_LINE:
                async_ops.extend((_short(ev.name), ev.start_ns, ev.duration_ns)
                                 for ev in line.events)
    every = [o for v in ops.values() for o in v]
    if start is None:
        start = min((o.start_ns for o in every), default=0.0)
    if stop is None:
        stop = max((o.start_ns + o.dur_ns for o in every), default=start)
    inside = {d: [o for o in v if o.start_ns + o.dur_ns > start and o.start_ns < stop]
              for d, v in ops.items()}
    copies: Dict[str, float] = collections.Counter()
    for name, s, d in async_ops:
        if s + d > start and s < stop:
            copies[name] += d / 1e9 / max(len(ops), 1)
    return Trace(window=(start, stop), ops=inside, host=host, hlo=hlo, copies=copies)


def summary(root: str) -> None:
    """Print every plane and line with event counts, then the longest ops
    with their stats: what one reads by hand before trusting the reader."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(root))
    for plane in pd.planes:
        lines = [(line.name, sum(1 for _ in line.events)) for line in plane.lines]
        print(f"plane {plane.name!r}: {lines}")
        for line in plane.lines:
            tot: Dict[str, float] = collections.Counter()
            first = {}
            for ev in line.events:
                tot[ev.name] += ev.duration_ns
                first.setdefault(ev.name, ev)
            for name, d in sorted(tot.items(), key=lambda kv: -kv[1])[:12]:
                stats = [(k, str(v)[:60]) for k, v in first[name].stats][:8]
                print(f"   {line.name!r}: {name[:70]!r} {d / 1e6:.3f} ms {stats}")
    t = load(root)
    print(f"window {t.window_s:.6f} s busy {t.busy_s():.6f} s")
    print("top ops", t.top_ops())
    print("gaps", t.idle_gaps())
    cats: Dict[str, float] = collections.Counter()
    for ops in t.ops.values():
        for o in ops:
            cats[o.category] += o.dur_ns / 1e9
    print("categories", sorted(cats.items(), key=lambda kv: -kv[1]))
    print("custom calls", t.hlo)
    print("async copies", sorted(t.copies.items(), key=lambda kv: -kv[1])[:12])


if __name__ == "__main__":
    summary(sys.argv[1])
