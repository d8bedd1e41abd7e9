"""The trace reduction, on a small trace recorded on a TPU v5e.

``data/small.xplane.pb`` (``record_trace.py``): three calls of one program
holding the ``cross_gram`` Pallas kernel (U (10, 8192) against V (100,
8192)) and a 5x5 convolution with its gradient, with a 10 ms host sleep
after each of the first two.  The host's source paths in it were rewritten
to a neutral prefix of the same length.
"""
import os
from types import SimpleNamespace

import pytest

import devtrace
import run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def trace():
    return devtrace.load(DATA)


def test_window_and_busy(trace):
    # three ~25 us programs and two 10 ms sleeps between start and stop
    assert 0.020 < trace.window_s < 0.030
    assert 0 < trace.busy_s() < 0.001
    assert list(trace.ops) == [0]


def test_ops_named_and_categorized(trace):
    names = {o.name for o in trace.ops[0]}
    assert "cross_gram.1" in names
    cats = {o.name: o.category for o in trace.ops[0]}
    assert cats["cross_gram.1"] == "custom-call"
    assert cats["fusion.4"] == "convolution fusion"
    n, secs = trace.op_seconds(lambda o: o.name.startswith("cross_gram"))
    assert n == 3 and 0 < secs < 1e-4


def test_idle_gaps_name_the_host(trace):
    gaps = trace.idle_gaps(2)
    assert [g[0] for g in gaps] == ["$time sleep", "$time sleep"]
    assert all(0.009 < g[1] < 0.015 for g in gaps)


def test_union_counts_overlap_once():
    assert devtrace.union_ns([(0, 10), (5, 15), (20, 30)], (0, 100)) == 25
    assert devtrace.union_ns([(0, 10)], (5, 8)) == 3


def test_readers(trace):
    r = SimpleNamespace(trace=trace, round_s=trace.window_s, window_s=1.0, peak=None,
                        model_flops_window=0.0, notes=[])
    idle = run.load_metric("device_idle_share").read(r)
    assert 95 < idle < 100
    conv = run.load_metric("conv_ms_per_round").read(r)
    assert 0 < conv < 0.1                    # one "round": the whole window
    # no peaks for this reading: the roofline readers return nothing
    assert run.load_metric("cross_gram_roofline").read(r) is None
    assert run.load_metric("round_mfu").read(r) is None


def test_cross_gram_roofline_skips_an_on_chip_map(trace):
    """XLA placed both operands on chip before each call: the kernel's
    1.9 us says nothing of HBM, so the reader reads nothing."""
    import roofline

    reader = run.load_metric("cross_gram_roofline")
    assert reader.placement(trace.hlo["cross_gram.1"]) == [
        ((10, 100), False), ((10, 8192), True), ((100, 8192), True)]
    r = SimpleNamespace(trace=trace, peak=roofline.peaks("TPU v5 lite"), notes=[])
    assert reader.read(r) is None


def test_cross_gram_placement_from_a_chip_trace():
    # a kernel as a v5e trace names it (an M = 2,000 EMNIST job): U
    # prefetched on chip, the (2000, 246590) V map read from HBM
    text = ("%cross_gram.12 = f32[20,2000]{1,0:T(8,128)S(1)} custom-call("
            "f32[20,246590]{1,0:T(8,128)S(1)} %copy-done.15, "
            "f32[2000,246590]{1,0:T(8,128)} %get-tuple-element.1454), "
            'custom_call_target="tpu_custom_call"')
    reader = run.load_metric("cross_gram_roofline")
    assert reader.placement(text) == [((20, 2000), True), ((20, 246590), True),
                                      ((2000, 246590), False)]
