"""Wall seconds of the compiler's passes and of the generated program's
executor, with their host spans.

Every pass function runs under :func:`timed_pass`, which writes a host
span ``omp.pass.<name>`` into a running profiler trace (a
``jax.profiler.TraceAnnotation`` costs nothing while none runs).  Inside
``with PassClock() as clock:`` the same calls also add their wall
seconds to ``clock.seconds``; :func:`repro.core.api.compile` keeps one
clock per build and stores the seconds on each
:class:`~repro.core.api.PassRecord`.  Passes nest (``plan_region`` runs
the per-stage analyze/schedule/plan_comm passes inside ``plan``): a pass
entered inside another pauses the outer one, so each second is counted
once, for the innermost pass, and the seconds sum to the time spent in
passes.

:func:`stats` gives the process-wide totals: pass seconds of every
finished clock, the entries into the generated program's executor
that ``Compiled.run`` records (:func:`add_executor`), how many
lax stages the executor traced on the sliced chunk evaluator or on the
chunk scan (:func:`count_chunk_eval`), and how many stages it traced
with a read through an index array (:func:`count_indirect`).
"""
from __future__ import annotations

import contextlib
import contextvars
import threading
import time

from jax.profiler import TraceAnnotation

_CLOCK: contextvars.ContextVar = contextvars.ContextVar(
    "omp_pass_clock", default=None)
_LOCK = threading.Lock()
_PASS_SECONDS: dict[str, float] = {}
_EXECUTOR = {"runs": 0, "seconds": 0.0}
_CHUNK_EVAL = {"sliced": 0, "scan": 0}
_INDIRECT = {"reads": 0, "elems": 0}
_CHUNK_TALLY: contextvars.ContextVar = contextvars.ContextVar(
    "omp_chunk_tally", default=None)


class PassClock:
    """Wall seconds per pass name of the passes run while it is entered
    (in this thread or task only: the clock is a context variable)."""

    def __init__(self) -> None:
        self.seconds: dict[str, float] = {}
        self._open: list[list] = []     # [name, running since]
        self._token = None

    def __enter__(self) -> "PassClock":
        self._token = _CLOCK.set(self)
        return self

    def __exit__(self, *exc) -> None:
        _CLOCK.reset(self._token)
        with _LOCK:
            for name, s in self.seconds.items():
                _PASS_SECONDS[name] = _PASS_SECONDS.get(name, 0.0) + s

    def _charge(self, now: float) -> None:
        """Add the time since the innermost open pass last resumed."""
        top = self._open[-1]
        self.seconds[top[0]] = self.seconds.get(top[0], 0.0) + now - top[1]
        top[1] = now

    def _start(self, name: str) -> None:
        now = time.perf_counter()
        if self._open:
            self._charge(now)
        self._open.append([name, now])

    def _stop(self) -> None:
        now = time.perf_counter()
        self._charge(now)
        self._open.pop()
        if self._open:
            self._open[-1][1] = now


@contextlib.contextmanager
def timed_pass(name: str):
    """Run one pass (a ``with`` block, or a decorated function) under the
    host span ``omp.pass.<name>``, timed by the entered
    :class:`PassClock` if there is one."""
    clock = _CLOCK.get()
    with TraceAnnotation(f"omp.pass.{name}"):
        if clock is None:
            yield
            return
        clock._start(name)
        try:
            yield
        finally:
            clock._stop()


def add_executor(seconds: float) -> None:
    """Count one entry into a generated program's executor."""
    with _LOCK:
        _EXECUTOR["runs"] += 1
        _EXECUTOR["seconds"] += seconds


def count_chunk_eval(path: str) -> None:
    """Count one lax stage traced on the ``"sliced"`` chunk evaluator
    or the ``"scan"`` of vmapped chunks, in the process totals and in
    the innermost :func:`chunk_tally` if one is open."""
    with _LOCK:
        _CHUNK_EVAL[path] += 1
    tally = _CHUNK_TALLY.get()
    if tally is not None:
        tally[path] += 1


def count_indirect(elems: int) -> None:
    """Count one stage traced with a read through an index array, whose
    iteration space looks up ``elems`` index entries per call, in the
    process totals and in the innermost :func:`chunk_tally`."""
    with _LOCK:
        _INDIRECT["reads"] += 1
        _INDIRECT["elems"] += elems
    tally = _CHUNK_TALLY.get()
    if tally is not None:
        tally["indirect_reads"] += 1
        tally["indirect_elems"] += elems


@contextlib.contextmanager
def chunk_tally(tally: dict):
    """Count into ``tally`` (``{"sliced": n, "scan": n, "indirect_reads":
    n, "indirect_elems": n}``) the stages traced while the block runs
    (in this thread or task only)."""
    token = _CHUNK_TALLY.set(tally)
    try:
        yield
    finally:
        _CHUNK_TALLY.reset(token)


def stats() -> dict:
    """Process-wide totals: ``pass_seconds`` (pass name -> wall seconds
    of every finished :class:`PassClock`), ``executor_runs`` and
    ``executor_seconds`` (entries into generated programs' executors
    through ``Compiled.run``, and their wall seconds), and
    ``chunk_eval_sliced`` / ``chunk_eval_scan`` (lax stages traced on
    the sliced chunk evaluator / on the chunk scan), ``indirect_reads``
    and ``indirect_elems`` (stages traced with a read through an index
    array, and the index entries they look up per call)."""
    with _LOCK:
        return {"pass_seconds": dict(_PASS_SECONDS),
                "executor_runs": _EXECUTOR["runs"],
                "executor_seconds": _EXECUTOR["seconds"],
                "chunk_eval_sliced": _CHUNK_EVAL["sliced"],
                "chunk_eval_scan": _CHUNK_EVAL["scan"],
                "indirect_reads": _INDIRECT["reads"],
                "indirect_elems": _INDIRECT["elems"]}
