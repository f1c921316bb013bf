"""What the host did around the window, from ``/proc`` and ``/sys``: the
CPUs the process may use and their NUMA nodes, and across the window the
process's CPU seconds, its involuntary context switches, the CPU its main
thread ran on, and the machine's steal and idle time. Printed on an earlier
line of every run, so a run that reads slow can be told from its host."""
from __future__ import annotations

import os
from pathlib import Path


def _read(path: str) -> str:
    try:
        return Path(path).read_text()
    except OSError:
        return ""


def _cpu_list(cpus) -> str:
    cpus, out, i = sorted(cpus), [], 0
    while i < len(cpus):
        j = i
        while j + 1 < len(cpus) and cpus[j + 1] == cpus[j] + 1:
            j += 1
        out.append(str(cpus[i]) if i == j else f"{cpus[i]}-{cpus[j]}")
        i = j + 1
    return ",".join(out)


def describe() -> str:
    cpus = os.sched_getaffinity(0)
    nodes = []
    for d in sorted(Path("/sys/devices/system/node").glob("node[0-9]*")):
        nodes.append(f"{d.name} {_read(str(d / 'cpulist')).strip()}")
    return (f"host: {len(cpus)} cpus allowed ({_cpu_list(cpus)}); "
            f"numa: {'; '.join(nodes) or 'unknown'}; "
            f"loadavg {_read('/proc/loadavg').split(' ')[:3]}")


class Sample:
    """A reading at one instant; ``since(earlier)`` describes the span."""

    def __init__(self):
        t = os.times()
        self.cpu_s = t.user + t.system
        stat = _read("/proc/self/stat").rsplit(")", 1)[-1].split()
        self.cpu_now = int(stat[36]) if len(stat) > 36 else -1
        self.nvcsw = 0
        for line in _read("/proc/self/status").splitlines():
            if line.startswith("nonvoluntary_ctxt_switches"):
                self.nvcsw = int(line.split()[1])
        head = (_read("/proc/stat").splitlines() or ["cpu"])[0].split()[1:]
        ticks = [int(x) for x in head]
        hz = os.sysconf("SC_CLK_TCK")
        self.idle_s = (ticks[3] if len(ticks) > 3 else 0) / hz
        self.steal_s = (ticks[7] if len(ticks) > 7 else 0) / hz

    def since(self, a: "Sample") -> str:
        return (f"host in window: process cpu {self.cpu_s - a.cpu_s:.2f} s, "
                f"involuntary switches {self.nvcsw - a.nvcsw}, main thread "
                f"on cpu {a.cpu_now} then {self.cpu_now}; machine steal "
                f"{self.steal_s - a.steal_s:.2f} s, idle "
                f"{self.idle_s - a.idle_s:.2f} cpu-s")
