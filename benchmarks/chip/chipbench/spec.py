"""Find a cell's configuration, traffic mix, limits and per-layer metric
readers by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix, one cell's
limits or one per-layer metric is a file of its own under the benchmark's
directory, so a later cell or metric is an added file and an added entry,
never an edit:

  configs/<config>.json    sizes, source, reduced/assumed/departures; an
                           optional top-level ``"blocks"``: {modality:
                           block} for towers not of the block ``tower``
  reference/<block>.py     a tower block's plain reference: its leaves,
                           ``Tower`` and operation counts (see
                           ``reference/tower.py``)
  traffic/<traffic>.json   parameters of the mix; ``"loop"`` names its loop
  loops/<loop>.py          ``class Loop(base.Loop)``: data and drains of one
                           kind of traffic (see ``chipbench/base.py``)
  limits/<workload>.json   the limit of each number compared for correct
  end_to_end/<metric>.py   ``read(ctx) -> float | None`` for one end-to-end
  metrics/<metric>.py      ... and for one per-layer metric
  peaks.json               chip peaks keyed by ``device_kind``

A reader returns None where its cell has nothing for it to read.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH_DIR = Path(__file__).resolve().parents[1]       # benchmarks/chip
CHECKOUT = BENCH_DIR.parents[1]                        # the repo root


class SpecError(RuntimeError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    limits: dict
    chips: int
    end_to_end: List[dict]     # metrics this cell reports with --trace 0
    per_layer: List[dict]      # metrics this cell reports with --trace 1
    bench_dir: Path            # where its files were found


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise SpecError(f"missing {path}")
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    wl = metric.get("workloads")
    return wl is None or workload in wl


def load_cell(workload: str, *, root: Path = CHECKOUT,
              bench_dir: Optional[Path] = None) -> Cell:
    """The cell named ``workload`` in ``<root>/BENCHMARK.json``, with its
    files read from ``bench_dir`` (default: the directory of this harness
    under ``root``)."""
    bench_dir = bench_dir or (root / BENCH_DIR.relative_to(CHECKOUT))
    bench = _load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    return Cell(
        name=workload,
        config=_load_json(bench_dir / "configs" / f"{w['config']}.json"),
        traffic=_load_json(bench_dir / "traffic" / f"{w['traffic']}.json"),
        limits=_load_json(bench_dir / "limits" / f"{workload}.json"),
        chips=int(w["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        bench_dir=bench_dir)


def load_peaks(device_kind: str, *, bench_dir: Path = BENCH_DIR) -> dict:
    peaks = _load_json(bench_dir / "peaks.json")
    if device_kind not in peaks["kinds"]:
        raise SpecError(f"device kind {device_kind!r} is not in peaks.json "
                        f"(known: {sorted(peaks['kinds'])})")
    return peaks["kinds"][device_kind]


_MODULES: Dict[Path, object] = {}


def _module(kind: str, name: str, bench_dir: Path):
    """``<bench_dir>/<kind>/<name>.py``, loaded once per path."""
    path = (bench_dir / kind / f"{name}.py").resolve()
    if path not in _MODULES:
        if not path.is_file():
            raise SpecError(f"no {path} for {kind} entry {name!r}")
        mod_spec = importlib.util.spec_from_file_location(
            f"chipbench_{kind}_{len(_MODULES)}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


def reader(kind: str, name: str, *, bench_dir: Path = BENCH_DIR
           ) -> Callable[[dict], Optional[float]]:
    """``read(ctx)`` of ``<kind>/<name>.py`` (kind ``metrics`` or
    ``end_to_end``)."""
    return _module(kind, name, bench_dir).read


def metric_reader(name: str, *, bench_dir: Path = BENCH_DIR
                  ) -> Callable[[dict], Optional[float]]:
    return reader("metrics", name, bench_dir=bench_dir)


def loop_class(name: str, *, bench_dir: Path = BENCH_DIR):
    """The ``Loop`` class of ``loops/<name>.py``."""
    return _module("loops", name, bench_dir).Loop


def block(config: dict, modality: str, *, bench_dir: Path = BENCH_DIR):
    """The reference block module of ``config``'s ``modality`` tower:
    ``reference/<name>.py`` for the name the configuration's top-level
    ``"blocks"`` gives that modality, ``reference/tower.py`` where it gives
    none."""
    return _module("reference", config.get("blocks", {}).get(modality,
                                                             "tower"),
                   bench_dir)


def arch_spec(config: dict):
    """The program's ``ArchSpec`` for a configuration file, built with the
    program's own config classes (so the served path sees exactly what a
    registered architecture would give it). The harness's own keys, such
    as ``"blocks"``, stay out of it."""
    from repro.configs.base import (ArchSpec, MEMConfig, RecallConfig,
                                    ShapeConfig, TowerConfig)
    m = config["model"]
    model = MEMConfig(towers=tuple(TowerConfig(**t) for t in m["towers"]),
                      embed_dim=m["embed_dim"], dtype=m["dtype"],
                      norm_eps=m["norm_eps"],
                      logit_scale_init=m["logit_scale_init"])
    return ArchSpec(
        arch_id=config["name"], family="mem", model=model,
        shapes=(ShapeConfig("query_batch", "retrieval",
                            global_batch=config["query_batch"],
                            n_candidates=config["n_candidates"]),),
        recall=RecallConfig(**config["recall"]), source=config["source"])


def tower(config: dict, modality: str) -> dict:
    for t in config["model"]["towers"]:
        if t["modality"] == modality:
            return t
    raise SpecError(f"{config['name']} has no {modality} tower")


def exit_layers(config: dict, n_layers: int) -> List[int]:
    """Exit depths as the RECALL config defines them: every
    ``exit_interval`` layers, always including the last."""
    step = config["recall"]["exit_interval"]
    exits = list(range(step, n_layers, step))
    if not exits or exits[-1] != n_layers:
        exits.append(n_layers)
    return exits


def query_granularities(config: dict, n_layers: int) -> List[int]:
    """The query tower's exits the query path embeds at: ``G`` exits spread
    over the exit list, full depth last (the rule the query runtime uses)."""
    import numpy as np
    exits = exit_layers(config, n_layers)
    g = config["recall"]["query_granularities"]
    idx = np.unique(np.linspace(0, len(exits) - 1, g).round().astype(int))
    return [exits[i] for i in idx]
