"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

The input is plain data: planes of lines of events, each event a name, a
start and a duration in nanoseconds, as ``jax.profiler.ProfileData``
reads them.  ``planes_of`` turns a trace file into that form, so the
reduction itself runs on hand-made traces in the tests.

- busy time: the union of the intervals in which an operation ran on a
  device (its "XLA Ops" line, or its "XLA Modules" line where a device
  has no op line), averaged over the devices that ran anything;
- per-module time and calls: the "XLA Modules" events, by program name
  (``jit__superstep``, ``jit_step``, ...) with any numeric suffix cut;
- idle gaps: the intervals between busy ones on the first device, each
  named by the innermost host event that covers at least half of it.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)$")


def planes_of(path: str) -> List[dict]:
    """``[{"name", "lines": [{"name", "events": [(name, start, dur)]}]}]``
    from an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name,
                          "events": [(e.name, float(e.start_ns),
                                      float(e.duration_ns))
                                     for e in line.events]})
        out.append({"name": plane.name, "lines": lines})
    return out


def find_xplane(directory: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                            recursive=True))
    return hits[-1] if hits else None


def module_name(name: str) -> str:
    return _SUFFIX.sub("", name.strip())


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _device_planes(planes):
    return [p for p in planes if p["name"].startswith("/device:")
            and not p["name"].startswith("/device:CPU")]


def _line(plane, name):
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return None


def busy_intervals(plane) -> List[Tuple[float, float]]:
    evs = _line(plane, OPS_LINE)
    if not evs:
        evs = _line(plane, MODULES_LINE) or []
    return union([(s, s + d) for _, s, d in evs if d > 0])


def reduce(planes: List[dict], window_ns: Tuple[float, float]) -> dict:
    """Device busy time, per-module time and calls, and the longest idle
    gaps within ``window_ns`` (start, end)."""
    w0, w1 = window_ns
    devs = [p for p in _device_planes(planes)
            if (_line(p, OPS_LINE) or _line(p, MODULES_LINE))]
    busy_by_dev = []
    modules: Dict[str, Dict[str, float]] = {}
    for p in devs:
        iv = [(max(s, w0), min(e, w1)) for s, e in busy_intervals(p)
              if e > w0 and s < w1]
        busy_by_dev.append(sum(e - s for s, e in iv))
        for name, s, d in _line(p, MODULES_LINE) or []:
            if s + d <= w0 or s >= w1:
                continue
            m = modules.setdefault(module_name(name), {"s": 0.0, "calls": 0})
            m["s"] += (min(s + d, w1) - max(s, w0)) / 1e9
            m["calls"] += 1
    busy = busy_by_dev and sum(busy_by_dev) / len(busy_by_dev)
    gaps = idle_gaps(planes, devs[0], window_ns) if devs else []
    return {"window_s": (w1 - w0) / 1e9, "busy_s": (busy or 0.0) / 1e9,
            "devices": len(devs), "modules": modules, "gaps_top": gaps}


def _host_events(planes):
    names, starts, durs = [], [], []
    for p in planes:
        if not p["name"].startswith("/host:"):
            continue
        for ln in p["lines"]:
            for name, s, d in ln["events"]:
                if d > 0:
                    names.append(name)
                    starts.append(s)
                    durs.append(d)
    return names, np.asarray(starts), np.asarray(durs)


def idle_gaps(planes, device_plane, window_ns, min_ns=1e5):
    """Idle gaps of one device over ``min_ns``, longest first, each as
    [name, seconds]: the name of the innermost (shortest) host event that
    covers at least half of the gap."""
    w0, w1 = window_ns
    busy = [(s, e) for s, e in busy_intervals(device_plane)
            if e > w0 and s < w1]
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(max(edges[i], w0), min(edges[i + 1], w1))
            for i in range(0, len(edges) - 1, 2)]
    gaps = sorted(((s, e) for s, e in gaps if e - s >= min_ns),
                  key=lambda g: g[0] - g[1])
    names, hs, hd = _host_events(planes)
    out = []
    for s, e in gaps[:10]:
        name = "no host event"
        if hs.size:
            cover = np.minimum(e, hs + hd) - np.maximum(s, hs)
            most = np.flatnonzero(cover >= 0.5 * (e - s))
            if most.size:
                name = names[int(most[np.argmin(hd[most])])]
        out.append([name, (e - s) / 1e9])
    return out
