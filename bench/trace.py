"""Reduce a JAX profiler trace (`.xplane.pb`) to the benchmark's readings.

Device operations are the events of the `XLA Ops` line of each
`/device:TPU:<n>` plane.  On the CPU backend, which has no device plane,
they are the host events that carry an `hlo_op` stat.  Host spans are the
benchmark's own `TraceAnnotation`s, whose names start with `bench.`; the
span `bench.window` marks the measured window.

  busy_s       union of the device operations' intervals inside the
               window, averaged over the devices that ran any
  window_s     length of the window
  ops          device seconds per operation name inside the window
  idle_gaps    idle seconds inside the window, by the innermost host span
               that covers the middle of each gap
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
NO_SPAN = "no host span"
TOP = 10

Interval = Tuple[int, int]


def find_trace(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def _events(profile):
    """(device op events by device, host span events)."""
    devices: Dict[str, List[Tuple[str, int, int]]] = defaultdict(list)
    spans: List[Tuple[str, int, int]] = []
    tpu_planes = [p for p in profile.planes
                  if p.name.startswith("/device:TPU:")]
    for plane in tpu_planes:
        for line in plane.lines:
            if line.name == "XLA Ops":
                devices[plane.name] += [(e.name, int(e.start_ns),
                                         int(e.end_ns)) for e in line.events]
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    spans.append((e.name, int(e.start_ns), int(e.end_ns)))
                elif not tpu_planes and any(k == "hlo_op"
                                            for k, _ in e.stats):
                    devices["/host:CPU"].append(
                        (e.name, int(e.start_ns), int(e.end_ns)))
    return devices, spans


def _clip(iv: Interval, window: Interval):
    lo, hi = max(iv[0], window[0]), min(iv[1], window[1])
    return (lo, hi) if hi > lo else None


def union(intervals: List[Interval]) -> List[Interval]:
    merged: List[Interval] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], hi))
        else:
            merged.append((lo, hi))
    return merged


def _label(gap: Interval, spans) -> str:
    mid = (gap[0] + gap[1]) // 2
    covering = [(hi - lo, name) for name, lo, hi in spans
                if lo <= mid < hi and name != WINDOW_SPAN]
    return min(covering)[1] if covering else NO_SPAN


def reduce(profile) -> Dict:
    """Readings of one traced window (see the module's docstring)."""
    devices, spans = _events(profile)
    windows = [(lo, hi) for name, lo, hi in spans if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, "
                           f"found {len(windows)}")
    window = windows[0]
    ops: Dict[str, float] = defaultdict(float)
    gaps: Dict[str, float] = defaultdict(float)
    busy = []
    for events in devices.values():
        inside = []
        for name, lo, hi in events:
            iv = _clip((lo, hi), window)
            if iv:
                inside.append(iv)
                ops[name] += (iv[1] - iv[0]) * 1e-9
        if not inside:
            continue
        merged = union(inside)
        busy.append(sum(hi - lo for lo, hi in merged) * 1e-9)
        edges = [window[0]] + [t for iv in merged for t in iv] + [window[1]]
        for lo, hi in zip(edges[::2], edges[1::2]):
            if hi > lo:
                gaps[_label((lo, hi), spans)] += (hi - lo) * 1e-9
    window_s = (window[1] - window[0]) * 1e-9
    busy_s = sum(busy) / len(busy) if busy else 0.0
    top = lambda d: [[k, v] for k, v in sorted(d.items(),
                                               key=lambda kv: -kv[1])[:TOP]]
    return {"busy_s": busy_s, "window_s": window_s, "devices": len(busy),
            "ops": dict(ops), "breakdown": {"device_ops": top(ops),
                                            "idle_gaps": top(gaps)}}


def reduce_file(path: str) -> Dict:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path))
