"""Reduce a JAX profiler trace to device busy time, idle gaps and op time.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
keeps two things: every operation on the line ``XLA Ops`` of each TPU
plane, and the harness's own host spans (``bench.*``, written with
``jax.profiler.TraceAnnotation``).  The result is plain JSON, so a small
trace can be kept with the tests.  :func:`summarize` turns it into a
:class:`Summary` for the metric readers.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)")
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench."
TOP = 10


def load(trace_dir: str) -> dict:
    """The device ops and host spans of the one trace under
    ``trace_dir``: ``{"devices": {id: [[name, start_ns, dur_ns], ...]},
    "host": [[name, start_ns, dur_ns], ...]}``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} traces under {trace_dir}")
    devices, host = {}, []
    for plane in ProfileData.from_file(paths[0]).planes:
        match = DEVICE_PLANE.fullmatch(plane.name)
        for line in plane.lines:
            if match and line.name == OPS_LINE:
                devices[int(match[1])] = [
                    [op_name(e.name), e.start_ns, e.duration_ns]
                    for e in line.events]
            elif plane.name.startswith("/host:"):
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith(HOST_SPAN_PREFIX))
    return {"devices": devices, "host": host}


def op_name(hlo: str) -> str:
    """The instruction's name from its HLO text, which is how the TPU's
    trace names an op: ``"%omp_sweep1.1 = f32[...] custom-call(...)"``
    gives ``"omp_sweep1.1"``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _union(intervals):
    """Merged ``[start, end)`` intervals, sorted."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _self_times(ops):
    """``(name, self time)`` of each op.  Ops nest on the trace's line
    (a ``while`` spans the ops of its body), so an op's own time is its
    duration less that of the ops directly inside it."""
    out, stack = [], []          # stack: [name, end, self time]
    for name, start, dur in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and start >= stack[-1][1]:
            out.append(stack.pop()[::2])
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    out.extend(entry[::2] for entry in reversed(stack))
    return out


@dataclasses.dataclass
class Summary:
    """A traced window, reduced.  Seconds are averaged over the chips."""
    busy_s: float
    window_s: float
    op_s: dict               # op name -> seconds of its own (self) time
    gaps: list               # [host span, seconds] of the longest gaps

    def ops_matching(self, predicate) -> float:
        return sum(s for name, s in self.op_s.items() if predicate(name))

    def breakdown(self) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [list(g) for g in self.gaps[:TOP]]}


def _host_label(spans, t):
    """The innermost host span around ``t``, or what lay between."""
    inner = None
    for name, start, dur in spans:
        if start <= t < start + dur and (inner is None or dur < inner[1]):
            inner = (name, dur)
    return inner[0] if inner else "host outside bench spans"


def summarize(trace: dict, device_ids: list, window_s: float) -> Summary:
    """Busy time, op time and idle gaps of the chips ``device_ids``.

    Busy time is the union of the chip's op intervals.  Idle gaps are
    taken on the first chip, between its first and last op, and named
    by the host span (``bench.call``, ``bench.block``...) that was open
    at the middle of the gap."""
    devices = {int(k): v for k, v in trace["devices"].items()}
    missing = [d for d in device_ids if not devices.get(d)]
    if missing:
        raise ValueError(f"no device ops traced on chips {missing}")
    busy, op_s = 0.0, collections.Counter()
    for d in device_ids:
        for name, self_ns in _self_times(devices[d]):
            op_s[name] += self_ns * 1e-9
        busy += sum(end - start for start, end in _union(
            (s, s + dur) for _n, s, dur in devices[d])) * 1e-9
    n = len(device_ids)
    merged = _union((s, s + dur) for _n, s, dur in devices[device_ids[0]])
    longest = sorted(zip(merged, merged[1:]),
                     key=lambda ab: ab[0][1] - ab[1][0])[:TOP]
    gaps = [[_host_label(trace["host"], (a[1] + b[0]) / 2),
             (b[0] - a[1]) * 1e-9] for a, b in longest]
    return Summary(busy_s=busy / n, window_s=window_s,
                   op_s={k: v / n for k, v in op_s.items()}, gaps=gaps)
