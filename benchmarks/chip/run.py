#!/usr/bin/env python3
"""Chip benchmark of the RECALL serving path: one cell, one run.

    python benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json``; its configuration,
traffic mix, traffic loop, limits and metric readers are files of their
own under this directory (see ``chipbench/spec.py``). A run:

  1. set-up: ``launch.serve.build_service`` at the configuration's
     published widths (weights made on the device from the seed), the
     traffic's data from the seed, then the traffic's own drains twice
     over as warm-up, so every shape the window uses is compiled;
  2. window: back-to-back drains of the served entry point for
     ``--seconds`` (traced by the profiler with ``--trace 1``), counting
     any compilation inside it;
  3. check: with the program's state freed, what the window's drains
     produced is compared with the plain float32 reference
     (``reference/``), each number against its limit;
  4. result: the check lines on stderr, then one JSON line on stdout:
     ``correct``, ``attempted``, ``failed``, ``metrics`` (end-to-end with
     ``--trace 0``, per-layer with ``--trace 1``), ``device`` and, traced,
     ``breakdown``; ``checks`` last.

Without a TPU, or with fewer chips than the cell asks for, or on a chip
kind ``peaks.json`` does not know, it exits non-zero and prints no result.
The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` or
``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

from chipbench import spec, verdict  # noqa: E402


class NoChip(RuntimeError):
    pass


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _devices(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX sees "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


class GcWatch:
    """Full (generation 2) collections of the garbage collector while
    ``on``: how many, and their seconds."""

    def __init__(self):
        self.on, self.times, self._t0 = False, [], None
        gc.callbacks.append(self._hear)

    def _hear(self, phase, info):
        if not self.on or info["generation"] != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.times.append(time.perf_counter() - self._t0)
            self._t0 = None


class CompileCounter:
    """Counts lowerings to an executable (a jit cache miss, whether the
    compile then hits the persistent cache or not) while ``on``."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        import jax
        self.on, self.n, self.names = False, 0, []
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **kw):
        if self.on and event == self.EVENT:
            self.n += 1
            self.names.append(str(kw.get("fun_name", "?")))


def run(argv=None, *, root: Path = spec.CHECKOUT, bench_dir=None,
        require_tpu: bool = True, peaks: dict = None) -> dict:
    """One run; returns the result dict (what ``main`` prints)."""
    args = _args(argv)
    cell = spec.load_cell(args.workload, root=root, bench_dir=bench_dir)
    import jax
    import numpy as np
    from repro.launch.serve import build_service, enable_compile_cache
    from chipbench import host, trace as TR
    cache_dir = enable_compile_cache()
    devs = _devices(cell.chips, require_tpu)
    dev = devs[0]
    peaks = peaks or spec.load_peaks(dev.device_kind)
    print(f"device: {dev.device_kind} x{len(devs)} ({dev.platform}); "
          f"compile cache {cache_dir}", flush=True)
    print(host.describe(), flush=True)
    counter, gcw = CompileCounter(), GcWatch()

    loop = spec.loop_class(cell.traffic["loop"],
                           bench_dir=cell.bench_dir)(cell, args.seed)
    with jax.profiler.TraceAnnotation("bench.setup"):
        with loop.phase("build_service"):
            engine, query, _ = build_service(
                spec.arch_spec(cell.config), seed=args.seed,
                **loop.service_kwargs(devs))
        loop.build(engine, query)
        loop.warm(int(cell.traffic["warmup_drains"]))
    setup_s = time.perf_counter() - T_START
    phases = ", ".join(f"{k} {v:.3f} s" for k, v in loop.phases.items())
    print(f"set-up: {setup_s:.3f} s ({phases})", flush=True)

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") \
        if args.trace else None
    if trace_dir:
        # host spans from TraceMe annotations only: the Python tracer would
        # slow the host work the per-layer metrics of this run time
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    lat, units, failed, drain_s = [], 0, 0, []
    counter.on = gcw.on = True
    host0 = host.Sample()
    loop.open_window()
    with jax.profiler.TraceAnnotation(TR.WINDOW_SPAN):
        t0 = time.perf_counter()
        t_end = t0 + args.seconds
        t_last = t0
        while time.perf_counter() < t_end:
            ts = time.perf_counter()
            with jax.profiler.TraceAnnotation(loop.SPAN):
                n, ok = loop.step()
            t_last = time.perf_counter()
            drain_s.append(t_last - ts)
            lat += [t_last - ts] * n
            units += n
            failed += 0 if ok else n
    counter.on = gcw.on = False
    host1 = host.Sample()
    loop.close_window()
    window_s = t_last - t0
    reduced = None
    if trace_dir:
        jax.profiler.stop_trace()
        reduced = TR.reduce(TR.from_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"window: {units} units in {window_s:.3f} s, "
          f"{loop.drain_no - loop.window_from} drains; compilations in "
          f"window: {counter.n} {sorted(set(counter.names))}", flush=True)
    ds = np.array(drain_s)
    slow = np.argsort(-ds)[:3]
    print(f"drain seconds: median {np.median(ds):.4f}, p99 "
          f"{np.percentile(ds, 99):.4f}, max {ds.max():.4f}; slowest "
          + ", ".join(f"#{i} {ds[i]:.4f} s at +{ds[:i].sum():.2f} s"
                      for i in slow)
          + f"; full collections in window: {len(gcw.times)}, "
          f"{sum(gcw.times):.4f} s", flush=True)
    print(host1.since(host0), flush=True)
    loop.report()
    mem = [d.memory_stats() or {} for d in devs]
    peak_bytes = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    work = loop.work(window_s, peaks)

    # free the program's state, then check against the reference
    t_check = time.perf_counter()
    loop.collect()
    loop.release()
    del engine, query
    gc.collect()
    loop.check()
    print(f"check: {time.perf_counter() - t_check:.3f} s", flush=True)

    try:
        within, checks = verdict.judge(cell.limits["numbers"], loop.readings)
    except ValueError as e:
        raise spec.SpecError(str(e))
    correct = within and failed == 0

    ctx = {"work": work, "trace": reduced, "peaks": peaks,
           "window_s": window_s, "units": units, "latencies_s": lat,
           "setup_s": setup_s, "loop": cell.traffic["loop"]}
    kind, wanted = (("metrics", cell.per_layer) if args.trace else
                    ("end_to_end", cell.end_to_end))
    values = {m["name"]: spec.reader(kind, m["name"],
                                     bench_dir=cell.bench_dir)(ctx)
              for m in wanted}
    if not args.trace and None in values.values():
        raise spec.SpecError(f"end-to-end metrics with nothing to read in "
                             f"{cell.name}: "
                             f"{[k for k, v in values.items() if v is None]}")
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in wanted if values[m["name"]] is not None}
    result = {"correct": bool(correct), "attempted": units, "failed": failed,
              "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(devs), "memory_peak_bytes": peak_bytes}}
    if reduced is not None:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["checks"] = checks
    result["compilations_in_window"] = counter.n
    return result


def main(argv=None) -> int:
    try:
        result = run(argv)
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    except spec.SpecError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    checks = result.pop("checks")
    n_comp = result.pop("compilations_in_window")
    result["checks"] = {k: [v["value"], v["limit"]] for k, v in
                        checks.items()}
    print(f"compilations in window: {n_comp}", file=sys.stderr)
    print(f"correct: {result['correct']} (failed {result['failed']} of "
          f"{result['attempted']})", file=sys.stderr)
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} <= {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
