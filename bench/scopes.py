"""Attribute a traced window's device time to the generated program's
own parts, and its idle gaps to what the runtime was doing.

The generated program names its parts with ``jax.named_scope``
(``omp.region.<name>`` / ``omp.block.<name>`` around the whole program,
and inside it ``omp.entry``, ``omp.stage.<stage>``, ``omp.kernel.<names>``,
``omp.exchange.<keys>``, ``omp.gather.<key>``, ``omp.combine[.<key>]``,
``omp.exit``).  XLA keeps each scope in the ``op_name`` metadata of the
compiled HLO, so :func:`scope_map` maps the instruction names that the
TPU's trace shows to scopes.  :func:`scope_seconds` then sums a
``trace.Summary``'s op self time per scope: nothing is lost, so the
scopes sum to the ops.

:func:`load_runtime` reads the runtime's own host events (PJRT execute,
``ReadSyncFlag``...) and the program's ``omp.*`` host spans from the
trace, and :func:`idle_gap_host` names each long device idle gap by the
runtime event that overlaps it most.  ``bench/trace.py`` keeps the
harness's spans only; this module adds to it and changes none of its
readings.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re

from bench import harness
from bench import trace as trace_mod

NO_SCOPE = "(no omp scope)"
OUTER = ("omp.region.", "omp.block.")
# Scope prefixes of the per-layer metrics that read them.
STAGE = ("omp.stage.", "omp.kernel.")
LAYOUT = ("omp.entry", "omp.exit")
EXCHANGE = ("omp.exchange.", "omp.gather.", "omp.combine")

_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([^\s=]+) = (.*)$")
_NAME_REF = re.compile(r"%([^\s,(){}]+)")


def scope_of(op_name: str) -> str | None:
    """The scope of an op from its ``op_name`` path: the innermost
    ``omp.`` component other than ``omp.region.*`` / ``omp.block.*``;
    failing that the region or block; else ``None``."""
    parts = [p for p in op_name.split("/") if p.startswith("omp.")]
    inner = [p for p in parts if not p.startswith(OUTER)]
    if inner:
        return inner[-1]
    return parts[-1] if parts else None


def _rank(scope: str | None) -> int:
    if scope is None:
        return 0
    return 1 if scope.startswith(OUTER) else 2


def scope_map(hlo_text: str) -> dict:
    """Instruction name -> scope, for every instruction of the compiled
    module's text (``Compiled.as_text()``).

    An instruction whose metadata names no inner scope, inside a loop
    body, branch or other computation that another instruction runs,
    takes that instruction's scope when it is narrower: the copies XLA
    adds inside a stage's chunk loop belong to that stage."""
    comps, current, entry = {}, None, None
    for line in hlo_text.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            head = line.split()[1] if line.startswith("ENTRY ") \
                else line.split()[0]
            current = head.lstrip("%")
            comps[current] = []
            if line.startswith("ENTRY "):
                entry = current
            continue
        m = _INSTR.match(line)
        if m and current is not None:
            meta = _OP_NAME.search(m[2])
            comps[current].append(
                (m[1], scope_of(meta[1]) if meta else None,
                 _NAME_REF.findall(m[2])))
    if entry is None:
        return {}
    inherited = {entry: None}
    out = {}
    todo = [entry]
    while todo:
        comp = todo.pop()
        outer = inherited[comp]
        for name, own, refs in comps[comp]:
            scope = own if _rank(own) >= _rank(outer) else outer
            out[name] = scope or NO_SCOPE
            for ref in refs:
                if ref in comps and ref not in inherited:
                    inherited[ref] = scope
                    todo.append(ref)
    return out


def scope_seconds(op_s: dict, smap: dict) -> dict:
    """Seconds per scope from seconds per op (a ``Summary.op_s``); an op
    the map does not name counts under :data:`NO_SCOPE`."""
    out = collections.Counter()
    for name, s in op_s.items():
        out[smap.get(name, NO_SCOPE)] += s
    return dict(out)


def seconds_under(scope_s: dict, prefixes: tuple) -> float:
    return sum(s for k, s in scope_s.items() if k.startswith(prefixes))


def top(scope_s: dict, n: int = trace_mod.TOP) -> list:
    """The ``n`` scopes with the most time, as ``[scope, seconds]``."""
    return [[k, v] for k, v in
            sorted(scope_s.items(), key=lambda kv: -kv[1])[:n]]


def _is_runtime(name: str) -> bool:
    return not name.startswith(("$", trace_mod.HOST_SPAN_PREFIX, "omp."))


def load_runtime(trace_dir: str, devices: dict) -> dict:
    """The host side of the one trace under ``trace_dir`` that
    ``trace.load`` leaves out: ``{"runtime": [...], "omp": [...]}``,
    each ``[name, start_ns, dur_ns]``.  ``runtime`` holds the events of
    the ``/host:CPU`` threads other than Python frames (``$...``) and
    spans (``bench.*``, ``omp.*``) that overlap an idle gap of some chip
    of ``devices`` (``trace.load(trace_dir)["devices"]``); ``omp`` holds
    the program's ``omp.*`` host spans."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} traces under {trace_dir}")
    gaps = [sorted(_gaps(ops)) for ops in devices.values()]
    ends = [[g[1] for g in chip] for chip in gaps]
    runtime, omp = [], []
    for plane in ProfileData.from_file(paths[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                ev = [e.name, e.start_ns, e.duration_ns]
                if e.name.startswith("omp."):
                    omp.append(ev)
                elif (plane.name == "/host:CPU" and _is_runtime(e.name)
                      and any(_meets_gap(ev, g, x)
                              for g, x in zip(gaps, ends))):
                    runtime.append(ev)
    return {"runtime": runtime, "omp": omp}


def _gaps(ops) -> list:
    """``[start, end)`` of the idle gaps between a chip's first and last
    op, longest first (as ``trace.summarize`` takes them)."""
    merged = trace_mod._union((s, s + d) for _n, s, d in ops)
    return sorted(([a[1], b[0]] for a, b in zip(merged, merged[1:])),
                  key=lambda g: g[0] - g[1])


def _overlap(event, gap) -> float:
    _name, start, dur = event
    return min(gap[1], start + dur) - max(gap[0], start)


def _meets_gap(event, gaps, ends) -> bool:
    """Whether ``event`` overlaps one of ``gaps`` (disjoint, sorted by
    start; ``ends`` their ends)."""
    i = bisect.bisect_right(ends, event[1])
    return i < len(gaps) and _overlap(event, gaps[i]) > 0


def idle_gap_host(trace: dict, runtime: list, device_id: int,
                  n: int = trace_mod.TOP) -> list:
    """For each of the ``n`` longest idle gaps on chip ``device_id``:
    ``[runtime event overlapping it most, overlap s, gap s]`` (the
    event is ``None`` where no runtime event overlaps the gap)."""
    devices = {int(k): v for k, v in trace["devices"].items()}
    out = []
    for gap in _gaps(devices[device_id])[:n]:
        best, most = None, 0.0
        for ev in runtime:
            o = _overlap(ev, gap)
            if o > most:
                best, most = ev[0], o
        out.append([best, most * 1e-9, (gap[1] - gap[0]) * 1e-9])
    return out


# ---------------------------------------------------------------------------
# What the per-layer metric readers share within one run
# ---------------------------------------------------------------------------

_RUN: dict = {}


def program_timing() -> dict | None:
    """The program's process-wide pass and executor totals
    (``omp.timing_stats()``) as they stood when a reader first asked:
    in ``run.py`` that is after set-up, before :func:`cell_scopes`
    builds the call again.  ``None`` where the program keeps none."""
    if "timing" not in _RUN:
        from repro import omp
        stats = getattr(omp, "timing_stats", None)
        _RUN["timing"] = stats() if stats is not None else None
    return _RUN["timing"]


def cell_scopes(reading) -> dict | None:
    """Seconds per scope of the traced window of ``reading``, or
    ``None`` where the compiled call names no ``omp.`` scope.

    The scope map comes from the cell's call built again as
    ``harness.build`` builds it, which JAX's compile cache serves: the
    same executable, so the same instruction names."""
    if reading.trace is None:
        return None
    key = reading.cell.name
    if _RUN.get("cell") != key:
        program_timing()
        devices = harness.cell_devices(reading.cell, require_tpu=False)
        smap = scope_map(harness.build(reading.cell, devices).call.as_text())
        _RUN.update(cell=key, smap=smap)
    smap = _RUN["smap"]
    if not any(s != NO_SCOPE for s in smap.values()):
        return None
    return scope_seconds(reading.trace.op_s, smap)


def per_call_ms(reading, prefixes: tuple) -> float | None:
    """Device ms per call per chip under the scopes ``prefixes``;
    nothing where no op of the window falls under them."""
    scope_s = cell_scopes(reading)
    if scope_s is None:
        return None
    s = seconds_under(scope_s, prefixes)
    return 1e3 * s / reading.window.calls if s > 0 else None
