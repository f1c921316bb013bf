"""The trace reduction, on a small hand-checked trace in the plain form
(``fixtures/trace_small.json``): busy union, idle gaps and their names,
kernel time by name, clipping to the window span."""
import json
from pathlib import Path

import pytest

from chipbench import trace as TR

FIX = Path(__file__).resolve().parent / "fixtures"


@pytest.fixture
def small():
    return json.loads((FIX / "trace_small.json").read_text())


def test_window_is_the_harness_span(small):
    assert TR.window_of(small) == (0, 10_000_000)


def test_busy_is_the_union_of_ops_inside_the_window(small):
    r = TR.reduce(small)
    # [1, 4] ms (fusion.1 overlaps the kernel), [6, 7], [9, 9.5]; copy.3
    # starts after the window closes
    assert r["busy_s"] == pytest.approx(4.5e-3)
    assert r["window_s"] == pytest.approx(10e-3)
    assert r["idle_share"] == pytest.approx(0.55)


def test_kernel_time_by_name(small):
    r = TR.reduce(small)
    assert TR.kernel_s(r, "topk_int4") == pytest.approx(2.0e-3)
    assert TR.kernel_s(r, "fusion") == pytest.approx(3.0e-3)
    assert TR.kernel_s(r, "no such op") == 0
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(2e-3)]


def test_idle_gaps_are_named_by_the_innermost_host_span(small):
    r = TR.reduce(small)
    gaps = r["idle_gaps"]
    # gaps: [0,1] [4,6] [7,9] [9.5,10] ms, longest first
    assert [g[1] for g in gaps] == pytest.approx([2e-3, 2e-3, 1e-3, 0.5e-3])
    names = {round(g[1] * 1e4): g[0] for g in gaps}
    assert gaps[0][0] == "query_batch"          # [4, 6]: midpoint 5.0
    assert gaps[1][0] == "PjitFunction(refine)"  # [7, 9]: midpoint 8.0
    assert names[10] == "query_batch"


def test_a_trace_without_device_ops_is_refused(small):
    small["device"] = {"/device:TPU:0": []}
    with pytest.raises(ValueError):
        TR.reduce(small)


def _sweep_busy(ops, lo, hi):
    """Busy time by an event sweep, independent of the reduction's
    interval merge."""
    ev = []
    for _, s, d in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            ev += [(a, 1), (b, -1)]
    busy, depth, last = 0.0, 0, None
    for t, step in sorted(ev):
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy * 1e-9


def test_recorded_chip_trace():
    """30 ms of a traced clip.recall-warm window on a TPU v5e."""
    tr = json.loads((FIX / "trace_recorded.json").read_text())
    r = TR.reduce(tr)
    lo, hi = TR.window_of(tr)
    ops = tr["device"]["/device:TPU:0"]
    assert r["busy_s"] == pytest.approx(_sweep_busy(ops, lo, hi))
    gaps = sum(g[1] for g in r["idle_gaps"])
    assert gaps <= r["window_s"] - r["busy_s"] + 1e-12
    assert 0 < r["idle_share"] < 1
    # the round-1 scan: the custom call over the (2^20, 384) int4 bank
    scan = TR.kernel_s(r, "custom-call", "s8[1048576,384]")
    assert scan == pytest.approx(sum(
        min(s + d, hi) - max(s, lo) for n, s, d in ops
        if "custom-call" in n and "s8[1048576,384]" in n) * 1e-9)
    assert scan > 0
    assert all(g[0] != "no host span" for g in r["idle_gaps"][:2])
    assert all(len(n) <= 240 for n, _ in r["device_ops"])
