"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to the numbers the
per-layer readers need: device busy and idle time, device time per
program and per operation, and idle gaps labelled by what the host was
doing.

The traced window is the span of the benchmark's own host annotations
(``bench_*``, one around every call the loop makes), so it is on the
same clock as the device events.  Busy time is the union of the
intervals in which an operation ran on a device, averaged over the
devices; idle is the rest of the window.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import pathlib
import re

#: host annotations the benchmark puts around its own calls
BENCH_PREFIX = "bench_"
#: the device line that holds one event per executed operation
OPS_LINE = "XLA Ops"
#: and the one that holds one event per executed program
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class DeviceTrace:
    window_s: float
    busy_s: float  # averaged over devices
    devices: int
    ops: dict  # op name -> device seconds (summed over devices)
    modules: dict  # program name -> device seconds (summed over devices)
    idle_by_host: dict  # host activity -> idle device seconds (averaged)

    def op_seconds(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(v for k, v in self.ops.items() if rx.search(k))

    def module_seconds(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return sum(v for k, v in self.modules.items() if rx.search(k))

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]
        return {
            "device_ops": [[k, v / self.devices] for k, v in top],
            "idle_gaps": [
                [k, v] for k, v in sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:n]
            ],
        }


def find_xplane(trace_dir) -> pathlib.Path:
    hits = sorted(glob.glob(str(pathlib.Path(trace_dir) / "**" / "*.xplane.pb"),
                            recursive=True))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return pathlib.Path(hits[-1])


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _module_name(name: str) -> str:
    """``jit_step(123)`` -> ``jit_step``: a program's name without its id."""
    return re.sub(r"\(\d+\)$", "", name)


def reduce(path, devices: int | None = None) -> DeviceTrace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    host_spans = []  # (start, end, name, depth order) from the host thread
    window = None
    dev_planes = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name and "Core" not in plane.name:
            dev_planes.append(plane)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                evs = [(e.start_ns, e.end_ns, e.name) for e in line.events]
                if not any(n.startswith(BENCH_PREFIX) for _, _, n in evs):
                    continue
                host_spans.extend(evs)
                bench = [(s, e) for s, e, n in evs if n.startswith(BENCH_PREFIX)]
                lo, hi = min(s for s, _ in bench), max(e for _, e in bench)
                window = (lo, hi) if window is None else (min(window[0], lo), max(window[1], hi))
    if window is None:
        raise ValueError("trace holds no benchmark host annotation")
    if devices is not None:
        dev_planes = dev_planes[:devices]
    if not dev_planes:
        raise ValueError("trace holds no device plane")
    lo, hi = window
    ops = collections.Counter()
    modules = collections.Counter()
    busy_total = 0.0
    idle_by = collections.Counter()
    host_spans.sort(key=lambda x: (x[0], -x[1]))
    starts = [x[0] for x in host_spans]
    for plane in dev_planes:
        intervals = []
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                s, t = max(e.start_ns, lo), min(e.end_ns, hi)
                if t <= s:
                    continue
                if line.name == OPS_LINE:
                    ops[e.name] += (t - s) * 1e-9
                    intervals.append((s, t))
                else:
                    modules[_module_name(e.name)] += (t - s) * 1e-9
        merged = _union(intervals)
        busy_total += sum(t - s for s, t in merged) * 1e-9
        prev = lo
        for s, t in merged + [[hi, hi]]:
            if s > prev:
                label = _host_label(host_spans, starts, (prev + s) / 2)
                idle_by[label] += (s - prev) * 1e-9
            prev = max(prev, t)
    n = len(dev_planes)
    return DeviceTrace(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total / n,
        devices=n,
        ops=dict(ops),
        modules=dict(modules),
        idle_by_host={k: v / n for k, v in idle_by.items()},
    )


def _host_label(spans, starts, t) -> str:
    """The innermost host event that covers time ``t``: walking back from
    the last event that started before ``t``, the first still open."""
    for j in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        if spans[j][1] >= t:
            return spans[j][2]
    return "(none)"
