"""The ``mla_attention_ms_per_round`` reader, on a synthetic trace and on
the small trace recorded on a TPU v5e (``data/small.xplane.pb``, which holds
no MLA kernel)."""
import os
from types import SimpleNamespace

import pytest

import devtrace
import run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.fixture(scope="module")
def trace():
    return devtrace.load(DATA)


def test_mla_attention_ms_per_round_sums_the_three_kernels():
    """A synthetic window of 2 s over 4 rounds: the forward, dQ and dK/dV
    calls count (one of them on each of two devices), nothing else does."""
    op = lambda name, start, dur, cat="custom-call": devtrace.Op(name, start, dur, cat)
    ops = [op("mla_attention_fwd.3", 0, 10e6), op("mla_attention_dq.1", 20e6, 20e6),
           op("mla_attention_dkv.2", 50e6, 30e6), op("moe_gmm.4", 90e6, 40e6),
           op("fusion.7", 140e6, 50e6, "convolution fusion"), op("while.1", 0, 1e9, "while")]
    t = devtrace.Trace(window=(0, 2e9), ops={0: ops, 1: ops}, host=[])
    r = SimpleNamespace(trace=t, round_s=0.5)
    assert run.load_metric("mla_attention_ms_per_round").read(r) == pytest.approx(60.0 / 4)


def test_mla_attention_ms_per_round_reads_nothing_without_the_kernel(trace):
    r = SimpleNamespace(trace=trace, round_s=trace.window_s)
    assert run.load_metric("mla_attention_ms_per_round").read(r) is None
    assert run.load_metric("mla_attention_ms_per_round").read(
        SimpleNamespace(trace=None, round_s=1.0)) is None
