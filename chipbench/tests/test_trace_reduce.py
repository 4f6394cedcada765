"""The trace reduction against a trace recorded on one TPU v5e: the traced
window of a yi9b-prefill-32k run with three prefill calls.  The kernel's
numbers (24 events, their summed durations) were read off the raw event
list of the device's "XLA Ops" line; the window and busy time are this
trace's as first reduced, kept so that a change to the reduction shows."""
from __future__ import annotations

import pathlib

import pytest

from chipbench import trace_reduce

TRACE = pathlib.Path(__file__).resolve().parents[1] / "testdata" / "prefill_32k_window.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce_file(TRACE)


def test_window_and_busy(summary):
    assert summary.devices == 1
    assert summary.window_s == pytest.approx(5.018792807, abs=1e-9)
    assert summary.busy_s == pytest.approx(5.012886192, abs=1e-9)
    # three calls in the window, so three gaps between device work of note
    big = [g for g in summary.gaps if g[0] > 1e-3]
    assert len(big) == 3 and {name for _, name in big} == {"wait"}


def test_kernel_time_and_calls(summary):
    # 3 calls x 8 layers of the Pallas kernel
    assert sum(n for name, n in summary.op_count.items()
               if trace_reduce.kernel_matches(name, "flash_attention_fwd")) == 24
    assert summary.kernel_s("flash_attention_fwd") == pytest.approx(3.238683597, abs=1e-9)
    assert summary.kernel_s("rwkv6_wkv_fwd") == 0


def test_nested_ops_count_self_time(summary):
    # the layer scan's while op spans its body; its self time is what the
    # body's ops leave uncovered, so ops' self times add up to the busy time
    assert sum(summary.op_s.values()) == pytest.approx(summary.busy_s, rel=1e-6)
    top = summary.breakdown()["device_ops"][0]
    assert top[0] == "flash_attention_fwd.6"


def test_spans(summary):
    assert set(summary.spans_s) == {"dispatch", "wait"}
    assert summary.spans_s["wait"] == pytest.approx(5.016742567, abs=1e-9)
