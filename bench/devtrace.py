"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time, time
per program and per operation, and idle gaps named by what the host was
doing in them.

Device planes are those named ``/device:<kind>:<n>``; their ``XLA Ops``
line holds one event per operation run, their ``XLA Modules`` line one per
program run.  The window is the host annotation ``WINDOW``; host
activity is the innermost host annotation whose name starts with
``HOST_PREFIX`` and covers a gap's middle.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

WINDOW = "bench.window"
HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:(?!CUSTOM)[A-Za-z]+:\d+$")
_RUN_ID = re.compile(r"\(\d+\)$")


@dataclass
class DeviceTrace:
    window_s: float
    busy_s: float  # mean over the device planes with work in the window
    n_devices: int
    op_s: dict = field(default_factory=dict)  # "<program>/<op>" -> seconds
    module_s: dict = field(default_factory=dict)  # program -> seconds
    idle_by_host: dict = field(default_factory=dict)  # host activity -> s

    def top(self, table: dict, n: int = 10) -> list:
        return [[k, v] for k, v in
                sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def find_xplane(log_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def program_name(event_name: str) -> str:
    """``jit_l2_topk_pallas(1234)`` -> ``jit_l2_topk_pallas``."""
    return _RUN_ID.sub("", event_name)


def op_name(event_name: str) -> str:
    """``%pad.1 = f32[...] pad(...)`` -> ``pad.1``: the HLO instruction."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def reduce_planes(planes) -> DeviceTrace | None:
    """``planes``: iterable of objects with ``name`` and ``lines``, each line
    with ``name`` and ``events`` (``name``, ``start_ns``, ``duration_ns``),
    as ``jax.profiler.ProfileData`` gives them.  None without a window
    annotation or without a device plane."""
    host_events: list = []
    devices: list = []
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host_events.append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name))
        elif _DEVICE_PLANE.match(plane.name):
            lines = {line.name: [(ev.name, ev.start_ns, ev.duration_ns)
                                 for ev in line.events]
                     for line in plane.lines}
            devices.append(lines)
    windows = [(s, e) for s, e, n in host_events if n == WINDOW]
    if not windows or not devices:
        return None
    lo, hi = windows[0]
    activities = sorted((s, e, n) for s, e, n in host_events if n != WINDOW)
    out = DeviceTrace(window_s=(hi - lo) / 1e9, busy_s=0.0, n_devices=0)
    busy_total = 0.0
    first_gaps = None
    for lines in devices:
        ops = [(n, s, s + d) for n, s, d in lines.get(OPS_LINE, [])
               if s + d > lo and s < hi]
        if not ops:
            continue
        out.n_devices += 1
        busy = _union(_clip([(s, e) for _, s, e in ops], lo, hi))
        busy_total += sum(e - s for s, e in busy) / 1e9
        mods = sorted((s, s + d, program_name(n))
                      for n, s, d in lines.get(MODULES_LINE, [])
                      if s + d > lo and s < hi)
        mod_starts = [m[0] for m in mods]
        for s, e, name in mods:
            span = (min(e, hi) - max(s, lo)) / 1e9
            out.module_s[name] = out.module_s.get(name, 0.0) + span
        for name, s, e in ops:
            mid = (s + e) / 2
            j = bisect.bisect_right(mod_starts, mid) - 1
            prog = mods[j][2] if j >= 0 and mods[j][1] >= mid else "?"
            key = f"{prog}/{op_name(name)}"
            out.op_s[key] = out.op_s.get(key, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
        if first_gaps is None:
            edges = [lo] + [x for iv in busy for x in iv] + [hi]
            first_gaps = [(edges[i], edges[i + 1])
                          for i in range(0, len(edges), 2)
                          if edges[i + 1] > edges[i]]
    if not out.n_devices:
        return None
    out.busy_s = busy_total / out.n_devices
    for s, e in first_gaps or []:
        mid = (s + e) / 2
        name = "other"
        best = None
        for a, b, n in activities:  # innermost = latest start covering mid
            if a > mid:
                break
            if b >= mid and (best is None or a >= best):
                best, name = a, n
        out.idle_by_host[name] = out.idle_by_host.get(name, 0.0) + (e - s) / 1e9
    return out


def idle_share(d: DeviceTrace | None) -> float | None:
    """Share of the window in which no operation ran on the device: 1 -
    (union of device-operation intervals / window), averaged over the
    chips used; None without a trace."""
    if d is None or d.window_s <= 0:
        return None
    return 1.0 - d.busy_s / d.window_s


def reduce_file(path: str) -> DeviceTrace | None:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes)
