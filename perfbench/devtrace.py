"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The profiler writes an XSpace (``<dir>/plugins/profile/<time>/*.xplane.pb``).
Its device planes are named ``/device:TPU:<n>``; each has an ``XLA Ops``
line whose events are the operations the device ran. The host plane
``/host:CPU`` holds, on the Python threads, the spans the harness writes
with ``jax.profiler.TraceAnnotation`` (names in ``SPANS``). Both run on one
clock, so a gap on the device can be put beside the span the host was in.

``reduce_space`` gives, over the traced window (the harness's ``window``
span):

  * ``busy_s``: per device, the length of the union of its op intervals;
  * ``window_s``: the window's length;
  * ``device_ops``: total device time per operation name, largest first;
  * ``idle_gaps``: the longest stretches with no op on a device, each named
    by the innermost harness span that covers its midpoint ("none" where
    the host was in no span).
"""
from __future__ import annotations

import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "window"
# every span the harness writes around a call into the program
SPANS = ("window", "next_batch", "step_dispatch", "step_wait",
         "final_sync", "generator_submit", "generator_sleep", "result_wait")


def merged(intervals) -> list:
    """Sorted, non-overlapping [start, end] cover of the intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def op_name(event_name: str) -> str:
    """``%fusion.20 = f32[...] fusion(...)`` -> ``fusion.20``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce_space(space, *, top: int = 10) -> dict:
    """Reduce a ``jax.profiler.ProfileData`` (or anything with the same
    planes/lines/events shape) to the window's device numbers."""
    devices, spans = {}, []
    for plane in space.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend(
                        (ev.start_ns, ev.start_ns + ev.duration_ns,
                         ev.name) for ev in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                              ev.name) for ev in line.events
                             if ev.name in SPANS)
    windows = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not devices or not windows:
        return {}
    lo = min(s for s, _ in windows)
    hi = max(e for _, e in windows)
    busy, ops, gaps = {}, {}, []
    inner = sorted(((s, e, n) for s, e, n in spans if n != WINDOW_SPAN),
                   key=lambda t: t[1] - t[0])
    for dev, evs in sorted(devices.items()):
        iv = merged(_clip([(s, e) for s, e, _ in evs], lo, hi))
        busy[dev] = sum(e - s for s, e in iv) * 1e-9
        for s, e, name in evs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                key = op_name(name)
                ops[key] = ops.get(key, 0.0) + d * 1e-9
        prev = lo
        for s, e in iv + [[hi, hi]]:
            if s > prev:
                mid = 0.5 * (prev + s)
                label = next((n for a, b, n in inner if a <= mid <= b),
                             "none")
                gaps.append((s - prev, label))
            prev = max(prev, e)
    window_s = (hi - lo) * 1e-9
    gaps.sort(key=lambda g: -g[0])
    return {
        "busy_s": busy,
        "window_s": window_s,
        "device_ops": [[k, v] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label, g * 1e-9] for g, label in gaps[:top]],
    }


def read_dir(trace_dir: str) -> dict:
    """Reduce the newest ``*.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return {}
    return reduce_space(ProfileData.from_file(files[-1]))


def idle_share(record: dict, kind: str):
    """Percent of a run's traced window with no op on the device, averaged
    over the chips in use; None where the run is not of ``kind`` or no
    trace was read."""
    tr = record.get("trace")
    if record["kind"] != kind or not tr or not tr.get("busy_s"):
        return None
    busy = list(tr["busy_s"].values())
    return 100.0 * (1.0 - (sum(busy) / len(busy)) / tr["window_s"])
