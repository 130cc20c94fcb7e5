"""Reduction of the program's own spans and scopes in a JAX profiler trace.

``devtrace.py`` reduces the device's operations and the harness's spans.
This module reads, from the same XSpace and on the same clock, what the
program writes itself:

  * host spans ``serve.*`` (the serve worker) and ``data.*`` (the input
    pipeline), written with ``repro.profiling.span``; a host line is named
    by the spans it carries (``ROLES``), never by its thread's name;
  * the ``jax.named_scope`` names on the device's operations (``SCOPES``),
    found as components of each operation's name path, whatever transform
    wrappers (``jvp(...)``, ``transpose(jvp(...))``, ``vmap(...)``) enclose
    them, so a backward op counts under its forward scope.

``reduce_program`` gives, over the harness's ``window`` span:

  * ``window_s``: the window's length, as ``devtrace`` has it;
  * ``host_spans``: per line role, per program span name, its count and its
    seconds inside the window;
  * ``span_cover_s``: per line role, the seconds of the window that some
    program span on the line covers;
  * ``device_scopes``: device seconds per scope, summed over layers, over
    forward and backward and over the chips in use, with ``unscoped`` for
    the rest, through the optimized HLO's ``op_name`` of each operation
    (``hlo_op_paths``), as the trace's op events do not carry it;
  * ``idle_by_span``: device-idle seconds, summed over the chips in use, by
    the innermost program span on the serve worker's or the producer's line
    that covers each idle instant (``none`` where none does);
  * ``step_module``, ``step_executions``: the module with the most device
    time on the ``XLA Modules`` line, and how many of its executions the
    window holds (a clipped one by its clipped fraction).

The ``read_*`` functions below turn a run's record, whose ``trace`` holds
these keys besides ``devtrace``'s, into the per-layer numbers that PERF.md
names; each returns None for a record of the other kind or with nothing to
read.
"""
from __future__ import annotations

import re

from perfbench.devtrace import (DEVICE_PREFIX, HOST_PLANE, OPS_LINE,
                                WINDOW_SPAN, merged, op_name)

MODULES_LINE = "XLA Modules"
PROGRAM_PREFIXES = ("serve.", "data.")
# a host line's role: the first of these spans it carries names it
ROLES = (("serve.poll", "serve_worker"), ("data.draw", "data_producer"),
         (WINDOW_SPAN, "main"))
# the lines whose spans idle time is put down to
IDLE_ROLES = ("serve_worker", "data_producer")
SCOPES = ("embed", "message", "node_update", "heads", "loss", "optimizer")
UNSCOPED = "unscoped"
NO_SPAN = "none"
# the serve worker's stages in which the host, not a request, holds the
# device idle
SERVE_HOST_STAGES = ("serve.file", "serve.assemble", "serve.dispatch",
                     "serve.scatter")
_WRAPPER = re.compile(r"^(?:[A-Za-z_]\w*\()+")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|to_apply)=%([\w.\-]+)")


def scope_of(path: str | None):
    """The innermost of ``SCOPES`` among the components of an op's name
    path, each stripped of transform wrappers; None where there is none.

    ``jit(step)/transpose(jvp(egnn))/layer0/message/dot_general`` ->
    ``message``."""
    if not path:
        return None
    found = None
    for part in path.split("/"):
        part = _WRAPPER.sub("", part).rstrip(")")
        if part in SCOPES:
            found = part
    return found


def hlo_op_paths(hlo_text: str) -> dict:
    """Instruction name -> name path, from an optimized HLO module's text
    (``compiled.as_text()``). An instruction's own ``op_name`` metadata
    where it names a scope; else, as for a fusion that XLA left without
    metadata, the first scoped path in the computations it calls (their
    ROOT first)."""
    comps, instrs = {}, {}    # computation -> [instr]; instr -> (path, calls)
    comp = None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            comp = line.removeprefix("ENTRY ").split(" ", 1)[0].lstrip("%")
            comps[comp] = []
            continue
        line = line.strip()
        root = line.startswith("ROOT ")
        line = line.removeprefix("ROOT ")
        if comp is None or " = " not in line:
            continue
        name = op_name(line)
        m = _OP_NAME.search(line)
        instrs[name] = (m.group(1) if m else None, _CALLS.findall(line))
        comps[comp].insert(0, name) if root else comps[comp].append(name)
    memo = {}

    def comp_path(c):
        if c not in memo:
            memo[c] = next((p for p in map(resolve, comps.get(c, ()))
                            if scope_of(p)), None)
        return memo[c]

    def resolve(name):
        path, calls = instrs[name]
        if scope_of(path):
            return path
        return next((p for p in map(comp_path, calls) if p), path)

    return {name: p for name in instrs if (p := resolve(name)) is not None}


def _clipped(s, e, lo, hi):
    return max(0, min(e, hi) - max(s, lo))


def _role(names) -> str | None:
    return next((role for span, role in ROLES if span in names), None)


def _host_lines(space) -> list:
    """(role, [(start, end, name)]) for each host line that carries a
    program span or the window."""
    out = []
    for plane in space.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            evs = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                   for ev in line.events
                   if ev.name == WINDOW_SPAN
                   or ev.name.startswith(PROGRAM_PREFIXES)]
            role = _role({n for _, _, n in evs})
            if role is not None:
                out.append((role, evs))
    return out


def _device_lines(space):
    """{device plane name: {line name: [(start, end, name)]}} for the ops
    and modules lines."""
    out = {}
    for plane in space.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        lines = out.setdefault(plane.name, {OPS_LINE: [], MODULES_LINE: []})
        for line in plane.lines:
            if line.name in lines:
                lines[line.name].extend(
                    (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                    for ev in line.events)
    return out


def _idle(ops, lo, hi) -> list:
    """The stretches of [lo, hi] in which no op runs."""
    busy = merged([(max(s, lo), min(e, hi)) for s, e, _ in ops
                   if e > lo and s < hi])
    out, prev = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > prev:
            out.append((prev, s))
        prev = max(prev, e)
    return out


def idle_by_span(idle, spans) -> dict:
    """Seconds of the ``idle`` stretches under the innermost (shortest) of
    ``spans`` [(start, end, name)] that covers each instant."""
    points = sorted({t for iv in idle for t in iv}
                    | {t for s, e, _ in spans for t in (s, e)})
    starts = sorted(spans)
    out, active, k = {}, [], 0
    idle = sorted(idle)
    j = 0
    for a, b in zip(points, points[1:]):
        while k < len(starts) and starts[k][0] <= a:
            active.append(starts[k])
            k += 1
        active = [sp for sp in active if sp[1] > a]
        while j < len(idle) and idle[j][1] <= a:
            j += 1
        if j == len(idle) or idle[j][0] > a:
            continue
        name = min(active, key=lambda sp: sp[1] - sp[0])[2] if active \
            else NO_SPAN
        out[name] = out.get(name, 0.0) + (b - a) * 1e-9
    return out


def reduce_program(space, op_paths: dict | None = None) -> dict:
    """Reduce a ``jax.profiler.ProfileData`` (or anything with the same
    planes/lines/events shape) to the program's spans and scopes over the
    window. ``op_paths`` maps an op's instruction name to its name path
    (``hlo_op_paths``): a TPU trace's op events carry the instruction's
    HLO text without its metadata; without it no ``device_scopes``."""
    lines = _host_lines(space)
    windows = [(s, e) for _, evs in lines for s, e, n in evs
               if n == WINDOW_SPAN]
    devices = _device_lines(space)
    if not windows:
        return {}
    lo = min(s for s, _ in windows)
    hi = max(e for _, e in windows)
    host, cover = {}, {}
    for role, evs in lines:
        spans = host.setdefault(role, {})
        for s, e, n in evs:
            d = _clipped(s, e, lo, hi)
            if n != WINDOW_SPAN and d > 0:
                c = spans.setdefault(n, {"count": 0, "s": 0.0})
                c["count"] += 1
                c["s"] += d * 1e-9
        union = merged([(max(s, lo), min(e, hi)) for s, e, n in evs
                        if n != WINDOW_SPAN and _clipped(s, e, lo, hi)])
        cover[role] = cover.get(role, 0.0) + sum(
            e - s for s, e in union) * 1e-9
    attributable = [sp for role, evs in lines if role in IDLE_ROLES
                    for sp in evs if sp[2] != WINDOW_SPAN]
    paths = op_paths or {}
    scopes, idle, modules = {}, {}, {}
    for lines_of in devices.values():
        for s, e, n in lines_of[OPS_LINE]:
            d = _clipped(s, e, lo, hi)
            if d > 0:
                key = scope_of(paths.get(op_name(n))) or UNSCOPED
                scopes[key] = scopes.get(key, 0.0) + d * 1e-9
        for name, t in idle_by_span(_idle(lines_of[OPS_LINE], lo, hi),
                                    attributable).items():
            idle[name] = idle.get(name, 0.0) + t
        for s, e, n in lines_of[MODULES_LINE]:
            d = _clipped(s, e, lo, hi)
            if d > 0:
                t, x = modules.get(n, (0.0, 0.0))
                modules[n] = (t + d * 1e-9, x + d / (e - s))
    out = {"window_s": (hi - lo) * 1e-9, "host_spans": host,
           "span_cover_s": cover, "idle_by_span": idle}
    if set(scopes) - {UNSCOPED}:
        out["device_scopes"] = scopes
    if modules:
        step = max(modules, key=lambda m: modules[m][0])
        out["step_module"] = step
        out["step_executions"] = modules[step][1]
    return out


# -- per-layer readings of a run's record --------------------------------

def _trace(record, kind):
    tr = record.get("trace")
    if record.get("kind") != kind or not tr:
        return None
    return tr


def _span_s(tr, role, *names) -> float:
    spans = tr.get("host_spans", {}).get(role, {})
    return sum(spans.get(n, {}).get("s", 0.0) for n in names)


def read_serve_worker_busy_share(record):
    """Percent of the window in which the serve worker was not waiting for
    work (``serve.poll``)."""
    tr = _trace(record, "serve")
    if tr is None or "serve_worker" not in tr.get("host_spans", {}):
        return None
    return 100.0 * (1.0 - _span_s(tr, "serve_worker", "serve.poll")
                    / tr["window_s"])


def read_device_idle_in_host_serve(record):
    """Percent of the window in which the device was idle while the serve
    worker filed, assembled, dispatched or scattered."""
    tr = _trace(record, "serve")
    if tr is None or "idle_by_span" not in tr:
        return None
    idle = tr["idle_by_span"]
    return 100.0 * sum(idle.get(n, 0.0) for n in SERVE_HOST_STAGES) \
        / tr["window_s"]


def _train_device_ms(record, *scopes):
    tr = _trace(record, "train")
    if tr is None or "device_scopes" not in tr \
            or not tr.get("step_executions"):
        return None
    ds = tr["device_scopes"]
    return 1e3 * sum(ds.get(s, 0.0) for s in scopes) / tr["step_executions"]


def read_train_device_ms_message(record):
    """Device ms per step under the ``message`` scopes."""
    return _train_device_ms(record, "message")


def read_train_device_ms_node_update(record):
    """Device ms per step under the ``node_update`` scopes."""
    return _train_device_ms(record, "node_update")


def read_train_device_ms_heads(record):
    """Device ms per step under the ``heads`` and ``loss`` scopes."""
    return _train_device_ms(record, "heads", "loss")


def read_train_input_produce_ms(record):
    """Mean ms per batch the producer spent drawing and placing it."""
    tr = _trace(record, "train")
    if tr is None:
        return None
    draws = tr.get("host_spans", {}).get("data_producer", {}).get(
        "data.draw", {}).get("count", 0)
    if not draws:
        return None
    return 1e3 * _span_s(tr, "data_producer", "data.draw", "data.place") \
        / draws


def _window_compilations(record, kind):
    if record.get("kind") != kind or record.get("window_compilations") \
            is None:
        return None
    return float(record["window_compilations"])


def read_window_compilations_train(record):
    """Compilations of the training step inside the window."""
    return _window_compilations(record, "train")


def read_window_compilations_serve(record):
    """Compilations of the serving forward inside the window."""
    return _window_compilations(record, "serve")


READERS = {
    "serve_worker_busy_share": read_serve_worker_busy_share,
    "device_idle_in_host.serve": read_device_idle_in_host_serve,
    "train_device_ms.message": read_train_device_ms_message,
    "train_device_ms.node_update": read_train_device_ms_node_update,
    "train_device_ms.heads": read_train_device_ms_heads,
    "train_input_produce_ms": read_train_input_produce_ms,
    "window_compilations.train": read_window_compilations_train,
    "window_compilations.serve": read_window_compilations_serve,
}
