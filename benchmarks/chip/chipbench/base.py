"""What every traffic loop shares. A loop is a file ``loops/<name>.py``
that exports a class ``Loop`` derived from ``base.Loop``; a traffic file
names it under ``"loop"`` and the harness finds it by that name, as it
finds configurations and metric readers. Its parameters are the rest of the
traffic file.

``run.py`` drives a loop through these calls, in order:

  ``service_kwargs(devs)``  extra arguments for ``build_service``
  ``build(engine, query)``  the traffic's data, from the seed (set-up)
  ``warm(n)``               the loop's own drains, before the window
  ``open_window()``, then ``step()`` until the window closes: one drain of
                            the served entry point, traced as ``SPAN``;
                            returns (units done, whether it checked out)
  ``close_window()``, ``report()`` (per-drain lines), ``work(...)``
                            (what the per-layer readers read)
  ``collect()``             store-side checks, before the state is freed
  ``release()``, then ``check()``: compare with the plain reference into
                            ``readings``, one entry per number in the
                            cell's limits file

``control(cell, seed)`` gives the same numbers for the reference put in
the program's place one precision lower (``control.py``).
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List


class Tap:
    """Wrap ``obj.name`` so ``record(*args, out)`` sees every call; ``undo``
    restores the original (and drops the tap's reference to ``obj``)."""

    def __init__(self, obj, name, record):
        self.obj, self.name = obj, name
        orig = getattr(obj, name)

        def wrapped(*a, **kw):
            out = orig(*a, **kw)
            record(*a, out=out, **kw)
            return out
        setattr(obj, name, wrapped)

    def undo(self):
        delattr(self.obj, self.name)   # the instance attribute shadowed it
        self.obj = None


class Loop:
    """Shared bookkeeping; a loop file fills in build/step/collect/check."""

    SPAN = "drain"              # the host span around each ``step``

    def __init__(self, cell, seed: int):
        self.cell, self.cfg, self.tr = cell, cell.config, cell.traffic
        self.seed = seed
        self.drain_no = 0           # drains run so far, warm-up included
        self.window_from = None     # first drain index of the window
        self.taps: List[Tap] = []
        self.readings: Dict[str, float] = {}
        self.phases: Dict[str, float] = {}     # set-up seconds by phase

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        yield
        self.phases[name] = self.phases.get(name, 0.0) + \
            time.perf_counter() - t0

    def service_kwargs(self, devs) -> dict:
        return {}

    def warm(self, n: int) -> None:
        for i in range(n):
            with self.phase(f"warm-up drain {i}"):
                self.step()

    def open_window(self) -> None:
        self.window_from = self.drain_no
        self.stats0 = self._stats()

    def close_window(self) -> None:
        self.stats1 = self._stats()

    def report(self) -> None:
        pass

    def release(self) -> None:
        for t in self.taps:
            t.undo()
        self.taps = []
        self.engine = self.query = self.store = None

    def _stats(self) -> dict:
        return {}

    @staticmethod
    def control(cell, seed: int) -> dict:
        raise NotImplementedError
