"""Per-drain readings of the program's own spans and counters
(``repro.core.spans``), for the per-layer metric readers.

The program keeps its spans in memory while a profiler session collects,
so the traced window of a ``--trace 1`` run is what ``spans.window()``
returns afterwards. A reading is the window's total over the number of the
loop's root spans (one per drain). It is None in an untraced run, for the
other loop, and where the program has no such span or counter (a program
without ``repro.core.spans`` included).
"""
from __future__ import annotations

from typing import Optional

# the root span of each loop's drain
ROOTS = {"ingest": "engine.drain", "query": "query.query_batch"}


def _window(ctx, loop: str) -> Optional[dict]:
    if ctx["trace"] is None or ctx["loop"] != loop:
        return None
    try:
        from repro.core import spans
    except ImportError:
        return None
    win = spans.window()
    root = win.get(ROOTS[loop])
    if not root or not root["n"]:
        return None
    return win


def ms(ctx, loop: str, *names: str) -> Optional[float]:
    """Milliseconds a drain of the spans ``names`` together."""
    win = _window(ctx, loop)
    if win is None or not all(n in win for n in names):
        return None
    return 1e3 * sum(win[n]["s"] for n in names) / win[ROOTS[loop]]["n"]


def crossing_mb(ctx, loop: str, name: str) -> Optional[float]:
    """MB (10^6 bytes) a drain crossing between host and device, both
    ways, under the span ``name``."""
    win = _window(ctx, loop)
    rec = None if win is None else win.get(name)
    if rec is None or ("h2d_bytes" not in rec and "d2h_bytes" not in rec):
        return None
    return (rec.get("h2d_bytes", 0) + rec.get("d2h_bytes", 0)) / 1e6 \
        / win[ROOTS[loop]]["n"]
