"""Reduce a profiler trace of the measured window to device metrics.

The trace is first brought to a plain form, ``{"device": {device name:
[[op name, start_ns, dur_ns], ...]}, "host": [[span name, start_ns,
dur_ns], ...]}``, from the ``.xplane.pb`` the JAX profiler writes (device
planes ``/device:*``, their ``XLA Ops`` line; host spans from ``/host:*``).
Everything after that works on the plain form, which is what the tests
feed it.

* busy: the union of a device's op intervals inside the window, averaged
  over devices; idle share = 1 - busy / window.
* kernel time: the summed durations of a device's ops whose name contains
  a pattern.
* idle gaps: the stretches of the window in which no op ran on a device,
  each named by the shortest host span that covers its middle.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import List, Optional, Tuple

WINDOW_SPAN = "bench.window"


def from_xplane(trace_dir: str) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"device": {}, "host": []}
    for plane in pd.planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:"):
            ops = [ln for ln in lines if ln.name == "XLA Ops"] or lines
            out["device"][plane.name] = [
                [op_name(e.name), float(e.start_ns), float(e.duration_ns)]
                for ln in ops for e in ln.events]
        elif plane.name.startswith("/host:"):
            for ln in lines:
                out["host"].extend([e.name, float(e.start_ns),
                                    float(e.duration_ns)] for e in ln.events)
    return out


def op_name(text: str, width: int = 240) -> str:
    """A device op's name for the reduction: its HLO text without layouts
    and index comments, cut to ``width`` characters, e.g.
    ``fn.1 = (f32[256,128], s32[256,128]) custom-call(s32[1] %bitcast.4,
    f32[256,1024] %select_maximum_fusion, s8[1048576,512] %packed.1, ...``
    so the same op aggregates across calls and shapes stay readable."""
    t = text.lstrip("%")
    t = re.sub(r"/\*[^*]*\*/", "", t)
    prev = None
    while prev != t:                       # nested {...} layout annotations
        prev, t = t, re.sub(r"\{[^{}]*\}", "", t)
    t = re.sub(r"\s+", " ", t.replace(" %", " ").replace("(%", "("))
    return t[:width]


def window_of(tr: dict) -> Tuple[float, float]:
    """(start_ns, end_ns) of the harness's window span."""
    spans = [h for h in tr["host"] if h[0] == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    _, s, d = max(spans, key=lambda h: h[2])
    return s, s + d


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clipped(ops, lo, hi):
    for name, s, d in ops:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def reduce(tr: dict, window: Optional[Tuple[float, float]] = None,
           top: int = 10) -> dict:
    """busy_s, window_s, idle share, per-op device seconds (all devices),
    the ``top`` ops and the ``top`` longest idle gaps with their names."""
    lo, hi = window or window_of(tr)
    window_s = (hi - lo) * 1e-9
    devices = [ops for ops in tr["device"].values() if ops]
    if not devices:
        raise ValueError("no device ops in the trace")
    busy, op_s, gaps = [], defaultdict(float), []
    for ops in devices:
        ivs = []
        for name, a, b in _clipped(ops, lo, hi):
            ivs.append((a, b))
            op_s[name] += (b - a) * 1e-9
        u = _union(ivs)
        busy.append(sum(b - a for a, b in u) * 1e-9)
        edges = [lo] + [x for iv in u for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    busy_s = sum(busy) / len(busy)
    gaps.sort(key=lambda g: g[0] - g[1])
    named = [[_host_name(tr["host"], (a + b) / 2), (b - a) * 1e-9]
             for a, b in gaps[:top]]
    ranked = sorted(op_s.items(), key=lambda kv: -kv[1])
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "op_s": dict(op_s),
            "device_ops": [[n, s] for n, s in ranked[:top]],
            "idle_gaps": named}


def kernel_s(reduced: dict, *needles: str) -> float:
    """Device seconds of the ops whose name contains every needle."""
    return sum(s for n, s in reduced["op_s"].items()
               if all(x in n for x in needles))


def _host_name(host, t: float) -> str:
    best, best_d = "no host span", float("inf")
    for name, s, d in host:
        if s <= t <= s + d and d < best_d and name != WINDOW_SPAN:
            best, best_d = name, d
    return best

