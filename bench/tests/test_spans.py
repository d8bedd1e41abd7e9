"""The readers of the program's host spans, on a trace built by hand.

A 100 ms window holds one traced chunk of a depth-2 pipeline.  Device 0
runs the previous chunk (0-40 ms, with a 0.5 ms device-side stall at
10-10.5), then the build's eager programs (45-46, 50-51, 60-62), then the
next chunk from 70 ms.  The host builds the next chunk meanwhile:
``fl.build.variants`` (8-62) queues its eager ops behind the running chunk
and is held until it ends at 40, then works 22 ms.  A Python-tracer frame
lies over the idle at 69-70, where the host is in no ``fl.`` span.
"""
from types import SimpleNamespace

import pytest

import devtrace
import run

MS = 1e6
OPS = [("fusion.1", 0, 10), ("fusion.2", 10.5, 40), ("concatenate.1", 45, 46),
       ("concatenate.1", 50, 51), ("concatenate.1", 60, 62), ("fusion.1", 70, 100)]
SPANS = [
    ("fl.build", 1, 64),
    ("fl.build.configs", 1, 3),
    ("fl.build.schedule", 3, 8),
    ("fl.build.variants", 8, 62),
    ("$_client.py:296 stack_freeze_flags", 41, 44),
    ("fl.build.place", 62, 63),
    ("fl.dispatch", 64, 66),
    ("fl.wait", 66, 68),
    ("fl.flush", 68, 69),
    ("$time sleep", 69, 70),
]
# build work: configs 2 + schedule 5 + variants 54 less 32 held + place 1
BUILD_MS = 30.0
# gaps between busy runs: 40-45, 46-50, 51-60 under variants (18), 62-63
# under place (1), 64-66 under dispatch (2), 68-69 under flush (1); not
# 63-64 (fl.build alone), 66-68 (fl.wait), 69-70 (no fl. span) or the
# stall at 10-10.5 (inside the chunk's run)
IDLE_MS = 22.0


def _run(spans=SPANS, ops=OPS, window=(0, 100), round_s=3.2):
    tr = devtrace.Trace(
        window=(window[0] * MS, window[1] * MS),
        ops={0: [devtrace.Op(name, s * MS, (e - s) * MS, "") for name, s, e in ops]},
        host=[(name, s * MS, e * MS) for name, s, e in spans],
    )
    return SimpleNamespace(trace=tr, round_s=round_s, window_s=tr.window_s,
                           mix={"chunk_rounds": 4}, peak=None, notes=[])


def _read(name, r):
    return run.load_metric(name).read(r)


def test_host_build_reads_phases_less_their_wait():
    assert _read("host_build_ms_per_chunk", _run()) == pytest.approx(BUILD_MS)


def test_idle_behind_host_reads_gaps_under_leaf_spans():
    r = _run()
    assert _read("idle_behind_host_ms_per_chunk", r) == pytest.approx(IDLE_MS)
    # no more than the window's idle per chunk (26.5 ms, the stall included)
    idle_ms = (r.trace.window_s - r.trace.busy_s()) * 1e3
    assert idle_ms == pytest.approx(26.5)
    assert _read("idle_behind_host_ms_per_chunk", r) <= idle_ms


@pytest.mark.parametrize("name", ["host_build_ms_per_chunk",
                                  "idle_behind_host_ms_per_chunk"])
def test_chunks_come_from_the_trace_not_the_host_clock(name):
    # a traced round_s that holds the trace's export changes nothing
    assert _read(name, _run(round_s=4.5)) == _read(name, _run())
    # two dispatches in the trace: the same work over two chunks
    two = SPANS + [("fl.dispatch", 99, 99.5)]
    assert _read(name, _run(two)) == pytest.approx(_read(name, _run()) / 2)


def test_idle_under_wait_or_tracer_frames_is_not_counted():
    spans = [("fl.dispatch", 0, 1), ("fl.wait", 40, 60),
             ("$time sleep", 60, 70), ("fl.build", 62, 70)]
    assert _read("idle_behind_host_ms_per_chunk", _run(spans)) == 0.0


def test_a_phase_cut_by_the_window_is_clipped():
    r = _run(window=(0, 50))
    # variants 8-50 less 32 held, with configs and schedule; place is out
    assert _read("host_build_ms_per_chunk", r) == pytest.approx(17.0)
    # 40-45 and 46-50
    assert _read("idle_behind_host_ms_per_chunk", r) == pytest.approx(9.0)


def test_device_work_the_phase_starts_is_not_a_wait():
    # a run begun after the phase began (the phase's own program) is its work
    ops = [("fusion.3", 20, 30)]
    spans = [("fl.build.variants", 10, 40), ("fl.dispatch", 40, 41)]
    assert _read("host_build_ms_per_chunk", _run(spans, ops)) == pytest.approx(30.0)


@pytest.mark.parametrize("name", ["host_build_ms_per_chunk",
                                  "idle_behind_host_ms_per_chunk"])
def test_incomplete_trace_reads_nothing(name):
    # a program without the spans: only the tracer's frames
    assert _read(name, _run([s for s in SPANS if s[0].startswith("$")])) is None
    # spans but no dispatch in the trace
    assert _read(name, _run([s for s in SPANS if s[0] != "fl.dispatch"])) is None
    # no window, or no trace at all
    assert _read(name, _run(window=(50, 50))) is None
    assert _read(name, SimpleNamespace(trace=None, round_s=1.0, mix={"chunk_rounds": 4})) is None
