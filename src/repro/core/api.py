"""``omp.compile`` — the one staged compiler entry point.

The paper frames OMP2MPI as a *compiler*: detect the annotated parallel
blocks, analyze them, plan the communication, emit the MPI program.
This module is that compiler's driver.  One call

    compiled = omp.compile(program, mesh, omp.Options(...))

accepts **either** a :class:`~repro.core.pragma.ParallelFor` **or** a
:class:`~repro.core.pragma.ParallelRegion` (rank-1 or rank-2) and runs
the explicit pass pipeline

    analyze  →  schedule  →  plan  →  plan_comm  →  schedule_comm  →  lower

recording each stage's input/output artifact on ``compiled.passes`` so
the intermediate representations are first-class (the lesson of the
staged follow-up systems — OMP2HMPP's instrumented variants, MPIrigen's
pipeline IRs) instead of reachable only by poking private helpers.

* **analyze**   — loop-nest canonicalisation + context analysis
  (:func:`repro.core.plan.analyze_program`),
* **schedule**  — chunking math, per axis
  (:func:`repro.core.plan.plan_schedule`),
* **plan**      — per-variable transfer strategies
  (:func:`repro.core.plan.decide_strategies`; for fused regions the
  inter-loop residency planner :func:`repro.core.region.plan_region`),
* **plan_comm** — cost-modeled boundary lowering
  (:class:`~repro.core.comm.BoundaryComm` per slab boundary),
* **schedule_comm** — region-wide communication scheduling
  (:class:`~repro.core.comm_schedule.CommSchedule`: aggregated
  ``ppermute`` payloads, fused reductions, prefetched exchanges),
* **lower**     — the executable artifact (the "generated MPI code"):
  a :class:`~repro.core.transform.DistributedProgram` or
  :class:`~repro.core.region.DistributedRegion` wrapped in
  :class:`Compiled`.

All knobs live on the frozen :class:`Options` dataclass — typed enums
instead of the historical string/bool kwargs soup — validated at
construction with actionable errors (:class:`CompileError`).  The
legacy entry points ``omp.to_mpi`` / ``omp.region_to_mpi`` survive as
thin shims that translate their kwargs to :class:`Options` and emit a
``DeprecationWarning``.

Compilation is cached: a structural key (program signature, mesh
shape/axes, Options, env shapes) lets repeated compiles — benchmark
sweeps, the differential harness — skip re-planning entirely.  The
cache is thread-safe for the concurrent compile service
(:mod:`repro.serving.compile_service`): warm hits never take the cache
lock, the miss path inserts and evicts under it.  With
:func:`enable_persistent_cache` (or ``$REPRO_AOT_CACHE_DIR``) compiled
executables additionally persist across processes through the
versioned AOT store (:mod:`repro.core.aot_store`): cold builds export
and save the XLA executable, fresh processes restore it instead of
re-planning and re-compiling.  Stats via :func:`compile_cache_stats`
(including disk hit/miss/bytes counters); ``benchmarks/run.py --json``
records the cold/warm split in its ``compile_cache`` section.

Each pass runs under the host span ``omp.pass.<name>`` and records its
wall seconds on its :class:`PassRecord`; ``Compiled.run`` counts and
times its entries into the executor (host span ``omp.executor``).
Process-wide totals: :func:`repro.core.timing.stats` (``omp.timing_stats``).
The generated program names its parts with ``jax.named_scope``
(``omp.region.*``/``omp.block.*``, ``omp.entry``, ``omp.stage.*``,
``omp.kernel.*``, ``omp.exchange.*``, ``omp.gather.*``, ``omp.combine*``,
``omp.exit``), which the compiled HLO keeps in each op's metadata.
"""
from __future__ import annotations

import dataclasses
import enum
import itertools
import math
import os
import threading
import time
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import aot_store as aot_store_mod
from repro.core import pragma
from repro.core import plan as plan_mod
from repro.core import timing
from repro.core.context import _aval_of
from repro.core.loop import LoopNotCanonical


class CompileError(LoopNotCanonical, ValueError):
    """Invalid :class:`Options` or an option × program combination the
    compiler cannot honor.

    Subclasses :class:`~repro.core.loop.LoopNotCanonical` (the paper's
    "block stays OpenMP" diagnostics path) *and* :class:`ValueError`
    (the historical kwargs-validation behavior), so the one new
    diagnostics path satisfies every legacy ``except`` clause.
    """


class Lowering(enum.Enum):
    """How the parallel block(s) are lowered to the device mesh."""

    FUSED = "fused"
    """One fused ``shard_map`` for the whole region; arrays stay
    resident across loop boundaries (the default).  A single
    ``ParallelFor`` has no boundaries to fuse, so this equals
    ``COLLECTIVE`` there."""

    COLLECTIVE = "collective"
    """TPU-native per-loop staging: chunk-cyclic slabs + balanced
    collectives, each loop transformed in isolation."""

    MASTER_WORKER = "master_worker"
    """Paper-faithful Fig. 1b staging: rank 0 owns the shared memory,
    all traffic moves through its links.  Rank-1 nests only."""

    PALLAS = "pallas"
    """The FUSED lowering with each compute span — a stage's chunk
    loop, or a chain of stages between scheduled exchanges — emitted as
    one tiled Pallas kernel over the local slab
    (:mod:`repro.core.pallas_lower`).  Kernels serve reads from chunk
    windows, so single blocks slice their inputs as regions do.  Off-TPU
    the kernels run in interpret mode (see ``Options.pallas_interpret``);
    on a TPU mesh every span compiles for the chip, or ``omp.compile``
    raises :class:`CompileError` naming the span."""


class CommMode(enum.Enum):
    """Boundary planner mode for fused regions."""

    AUTO = "auto"
    """Cheapest of resident / halo ``ppermute`` / all_gather /
    replicate per boundary (the cost model of :mod:`repro.core.comm`)."""

    GATHER = "gather"
    """All-gather-only boundaries — the measurable PR 1 baseline."""


class ShardPolicy(enum.Enum):
    """IN-buffer transfer policy for the per-loop staging lowerings
    (fused regions always plan sliced inputs — that is the point of
    residency)."""

    REPLICATE = "replicate"
    """The paper's rule: the master broadcasts every IN buffer."""

    SLICE = "slice"
    """Send each rank only its chunk slices (+ stencil halo rows)."""


def _coerce_enum(enum_cls, value, field):
    if isinstance(value, enum_cls):
        return value
    if isinstance(value, str):
        try:
            return enum_cls(value.lower())
        except ValueError:
            pass
    raise CompileError(
        f"Options.{field} must be one of "
        f"{[e.value for e in enum_cls]} (or a {enum_cls.__name__}), "
        f"got {value!r}")


def _normalize_chunk_weights(cw):
    """Canonicalise ``Options.chunk_weights``: a flat tuple of floats
    (rank-1), or a 2-tuple of per-axis entries (each a float tuple or
    ``None``) for ``collapse=2``."""

    def flat(seq, where):
        try:
            vals = tuple(float(x) for x in seq)
        except (TypeError, ValueError):
            raise CompileError(
                f"Options.chunk_weights{where} must be a sequence of "
                f"numbers, got {seq!r}") from None
        if not vals:
            raise CompileError(f"Options.chunk_weights{where} is empty")
        for v in vals:
            if not math.isfinite(v) or v <= 0:
                raise CompileError(
                    f"Options.chunk_weights{where} entries must be "
                    f"finite and > 0, got {vals}")
        return vals

    if not isinstance(cw, (tuple, list)):
        raise CompileError(
            "Options.chunk_weights must be a per-device weight vector "
            "(or a 2-tuple of per-axis vectors for collapse=2), got "
            f"{cw!r}")
    if any(e is None or isinstance(e, (tuple, list)) for e in cw):
        if len(cw) != 2 or not all(
                e is None or isinstance(e, (tuple, list)) for e in cw):
            raise CompileError(
                "per-axis Options.chunk_weights must be a 2-tuple of "
                f"weight vectors (or None per axis), got {cw!r}")
        return tuple(None if e is None else flat(e, f"[{d}]")
                     for d, e in enumerate(cw))
    return flat(cw, "")


@dataclasses.dataclass(frozen=True)
class Options:
    """Compilation options — the typed replacement for the historical
    ``to_mpi``/``region_to_mpi`` kwargs.

    Every field accepts the enum member or its string value; validation
    happens at construction and raises :class:`CompileError` with an
    actionable message.
    """

    axis: Any = None
    """Mesh axis clause: a name for rank-1 nests, a 2-tuple of distinct
    names for ``collapse=2``; ``None`` resolves the default
    (``"data"``, or ``("i", "j")`` for rank-2)."""

    lowering: Lowering = Lowering.FUSED
    comm: CommMode = CommMode.AUTO
    shard: ShardPolicy = ShardPolicy.REPLICATE

    comm_schedule: str = "aggregate"
    """The **schedule_comm** pass mode (:mod:`repro.core.comm_schedule`):
    ``"aggregate"`` (default) packs same-boundary ``ppermute`` payloads
    into one launch per direction, fuses per-stage reduction combines
    into flat collectives, and hoists each exchange to just after its
    producer (prefetch); ``"inline"`` pins the per-buffer behavior —
    same wire bytes, one launch per exchange — for measurement."""

    schedule: pragma.Schedule | None = None
    """Override every loop's ``schedule(...)`` clause at compile time
    (``None`` keeps the clauses written on the pragmas)."""

    keep_sharded: bool = False
    """Historical ``to_mpi`` flag that was silently ignored (and absent
    from ``region_to_mpi``).  Sharded-exit control is not implemented by
    any lowering — every lowering reassembles outputs to the
    shared-memory layout at exit — so ``True`` is rejected here instead
    of being dropped on the floor."""

    unroll_chunks: bool = False
    paper_master_excluded: bool | None = None

    pallas_interpret: bool | None = None
    """Pallas execution mode for ``Lowering.PALLAS``: ``None`` (default)
    runs the kernels in interpret mode off-TPU (CPU/CI) and compiled on
    TPU; ``True``/``False`` forces.  ``True`` is rejected on a TPU mesh,
    and the field under any other lowering."""

    chunk_weights: Any = None
    """Per-device speed weights for a straggler-weighted schedule
    (``runtime.straggler.rebalance_chunks`` apportions chunk ownership
    proportionally; faster devices run more chunks).  Rank-1: a
    sequence of P positive floats; ``collapse=2``: a 2-tuple of
    per-axis vectors (``None`` keeps an axis cyclic).  Collective
    chunk executor only — rejected under ``Lowering.MASTER_WORKER`` /
    ``Lowering.PALLAS`` and under ``Lowering.FUSED`` on regions (ring
    halo exchanges assume cyclic neighbors)."""

    def __post_init__(self) -> None:
        object.__setattr__(
            self, "lowering",
            _coerce_enum(Lowering, self.lowering, "lowering"))
        object.__setattr__(
            self, "comm", _coerce_enum(CommMode, self.comm, "comm"))
        object.__setattr__(
            self, "shard", _coerce_enum(ShardPolicy, self.shard, "shard"))

        cs = self.comm_schedule
        if isinstance(cs, str):
            cs = cs.lower()
        from repro.core.comm_schedule import SCHEDULE_MODES
        if cs not in SCHEDULE_MODES:
            raise CompileError(
                f"Options.comm_schedule must be one of {SCHEDULE_MODES}, "
                f"got {self.comm_schedule!r}")
        object.__setattr__(self, "comm_schedule", cs)

        sched = self.schedule
        if isinstance(sched, str):
            try:
                sched = pragma.Schedule(sched)
            except ValueError as e:
                raise CompileError(f"Options.schedule: {e}") from None
            object.__setattr__(self, "schedule", sched)
        elif sched is not None and not isinstance(sched, pragma.Schedule):
            raise CompileError(
                "Options.schedule must be a Schedule (omp.static()/"
                f"omp.dynamic()/omp.guided()) or None, got {sched!r}")

        if self.keep_sharded:
            raise CompileError(
                "Options.keep_sharded=True: sharded-exit control is not "
                "implemented by any lowering — outputs are always "
                "reassembled to the shared-memory layout at exit.  To keep "
                "arrays resident between loops, compile them as one "
                "omp.region(...) with Lowering.FUSED (the default)."
            )

        ax = self.axis
        if ax is not None:
            if isinstance(ax, list):
                ax = tuple(ax)
                object.__setattr__(self, "axis", ax)
            if isinstance(ax, tuple):
                if (len(ax) != 2 or not all(isinstance(a, str) for a in ax)
                        or ax[0] == ax[1]):
                    raise CompileError(
                        "Options.axis: a rank-2 axis clause must be a "
                        f"2-tuple of distinct mesh axis names, got {ax!r}")
            elif not isinstance(ax, str):
                raise CompileError(
                    "Options.axis must be a mesh axis name, a 2-tuple of "
                    f"names, or None, got {ax!r}")

        for field in ("unroll_chunks",):
            if not isinstance(getattr(self, field), bool):
                raise CompileError(
                    f"Options.{field} must be a bool, "
                    f"got {getattr(self, field)!r}")
        if self.paper_master_excluded not in (None, True, False):
            raise CompileError(
                "Options.paper_master_excluded must be True, False or None "
                f"(= derive from the lowering), got "
                f"{self.paper_master_excluded!r}")

        if self.pallas_interpret not in (None, True, False):
            raise CompileError(
                "Options.pallas_interpret must be True, False or None "
                f"(= interpret off-TPU), got {self.pallas_interpret!r}")
        if self.lowering is Lowering.PALLAS:
            if self.unroll_chunks:
                raise CompileError(
                    "Options.unroll_chunks has no effect under "
                    "Lowering.PALLAS: chunk compute runs as a tiled "
                    "Pallas kernel grid, not a lax.scan that could be "
                    "unrolled.  Drop unroll_chunks or use "
                    "Lowering.FUSED/COLLECTIVE.")
            if self.paper_master_excluded is not None:
                raise CompileError(
                    "Options.paper_master_excluded is a master/worker "
                    "staging knob; Lowering.PALLAS never stages through "
                    "a master rank (and Lowering.MASTER_WORKER has no "
                    "pallas variant).  Drop paper_master_excluded or "
                    "use Lowering.MASTER_WORKER.")
        elif self.pallas_interpret is not None:
            raise CompileError(
                "Options.pallas_interpret only applies to "
                "Lowering.PALLAS; this compile uses "
                f"lowering={self.lowering.value!r}.  Drop "
                "pallas_interpret or set lowering=\"pallas\".")

        if self.chunk_weights is not None:
            object.__setattr__(self, "chunk_weights",
                               _normalize_chunk_weights(self.chunk_weights))
            if self.lowering in (Lowering.MASTER_WORKER, Lowering.PALLAS):
                raise CompileError(
                    "Options.chunk_weights (straggler-weighted schedule) "
                    "requires the collective chunk executor; "
                    f"lowering={self.lowering.value!r} assumes cyclic "
                    "chunk ownership (explicit master/worker row math / "
                    "tiled kernel grids).  Use Lowering.COLLECTIVE, or "
                    "the default FUSED on a single block.")

    def describe(self) -> str:
        sched = (f"{self.schedule.kind}({self.schedule.chunk})"
                 if self.schedule is not None else "per-pragma")
        return (f"lowering={self.lowering.value} comm={self.comm.value} "
                f"comm_schedule={self.comm_schedule} "
                f"shard={self.shard.value} schedule={sched}")


# ---------------------------------------------------------------------------
# Pass records
# ---------------------------------------------------------------------------

PASS_NAMES = ("analyze", "schedule", "plan", "plan_comm", "schedule_comm",
              "lower")


@dataclasses.dataclass(frozen=True)
class PassRecord:
    """One pipeline stage: what went in, what came out, how long it took."""

    name: str
    input: str
    """Short description of the artifact(s) the pass consumed."""
    output: Any
    """The artifact the pass produced (consumed by the next pass)."""
    seconds: float = 0.0
    """Wall seconds of the pass (:mod:`repro.core.timing`).  Every
    record of a cache hit but ``lower`` is the build's it reuses, with
    that build's seconds; ``lower`` runs again on every hit and times
    itself."""

    def describe(self) -> str:
        out = self.output
        if isinstance(out, (tuple, list)):
            kind = f"{len(out)} artifact(s)"
        else:
            kind = type(out).__name__
        return (f"{self.name}: {self.input} -> {kind} "
                f"({1e3 * self.seconds:.1f} ms)")


# ---------------------------------------------------------------------------
# The structural compilation cache
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _Artifacts:
    """Mesh-independent result of the analyze→plan_comm passes; the
    ``program`` reference pins the ``id()``s used in the cache key."""

    passes: tuple[PassRecord, ...]
    exe_plan: Any           # DistPlan | RegionPlan | None (staged regions)
    program: Any


class _Counter:
    """Increment-only counter whose :meth:`inc` is a single C-level
    ``next()`` call — atomic under the GIL — so warm cache hits can
    count *exactly* without taking a lock (``_STATS[k] += 1`` is a
    read-modify-write that loses increments under threads).
    ``value`` peeks the iterator state without consuming it."""

    __slots__ = ("_it",)

    def __init__(self) -> None:
        self._it = itertools.count()

    def inc(self) -> None:
        next(self._it)

    @property
    def value(self) -> int:
        return self._it.__reduce__()[1][0]


class _Entry:
    """One cache line: the artifacts plus an LRU recency stamp.  Stamp
    refreshes are plain attribute stores (atomic under the GIL), so the
    hit path never locks; eviction — on the locked miss path — scans
    for the oldest stamp.  A racing stamp refresh during an eviction
    scan can at worst save a just-touched entry, never corrupt."""

    __slots__ = ("art", "stamp")

    def __init__(self, art: _Artifacts, stamp: int) -> None:
        self.art = art
        self.stamp = stamp


_CACHE: dict[tuple, _Entry] = {}
_CACHE_CAP = 512
_CACHE_LOCK = threading.Lock()   # guards the miss path: insert + evict
_TICK = itertools.count()        # LRU clock (atomic, see _Counter)
_HITS = _Counter()
_MISSES = _Counter()

# Persistent AOT executable store (None = in-memory only).  Enabled via
# enable_persistent_cache() or the REPRO_AOT_CACHE_DIR environment
# variable; EXPERIMENTS §Perf-I measures the cross-process warm start.
_PERSISTENT: aot_store_mod.AOTStore | None = None
_EXE_CACHE: dict[str, Any] = {}   # disk key -> loaded AOT executable
_UNEXPORTABLE: set[str] = set()   # disk keys whose executor cannot lower


def compile_cache_stats() -> dict:
    """Hit/miss counters and current size of the compilation cache,
    plus the persistent-store counters (``disk_hits`` / ``disk_misses``
    / ``disk_errors`` / ``disk_bytes_read`` / ``disk_bytes_written`` —
    zeros while persistence is disabled)."""
    stats = {"hits": _HITS.value, "misses": _MISSES.value,
             "size": len(_CACHE),
             "persistent_dir": _PERSISTENT.path if _PERSISTENT else None}
    stats.update(_PERSISTENT.stats if _PERSISTENT
                 else aot_store_mod.empty_stats())
    return stats


def clear_compile_cache() -> None:
    """Drop every cached compilation and reset the counters (the
    persistent store keeps its on-disk entries; its counters reset)."""
    global _HITS, _MISSES
    with _CACHE_LOCK:
        _CACHE.clear()
        _EXE_CACHE.clear()
        _UNEXPORTABLE.clear()
        _HITS = _Counter()
        _MISSES = _Counter()
        if _PERSISTENT is not None:
            _PERSISTENT.stats = aot_store_mod.empty_stats()


def enable_persistent_cache(path: str | None = None) -> str:
    """Turn on the on-disk AOT executable store at ``path`` (default:
    ``$REPRO_AOT_CACHE_DIR`` or ``~/.cache/repro-aot``).  Returns the
    resolved directory.  Compiles gain a disk probe on the miss path
    and an AOT export+save on cold builds; a fresh process pointed at
    the same directory restores executables instead of re-planning and
    re-compiling (EXPERIMENTS §Perf-I)."""
    global _PERSISTENT
    if path is None:
        path = os.environ.get(aot_store_mod.ENV_VAR) or os.path.join(
            os.path.expanduser("~"), ".cache", "repro-aot")
    _PERSISTENT = aot_store_mod.AOTStore(path)
    return _PERSISTENT.path


def disable_persistent_cache() -> None:
    """Back to in-memory-only caching (on-disk entries are kept)."""
    global _PERSISTENT
    _PERSISTENT = None
    _EXE_CACHE.clear()
    _UNEXPORTABLE.clear()


def _program_signature(p) -> tuple:
    """Structural identity of a program.  Bodies are compared by
    ``id()``; cache entries keep a strong reference to the program so
    the ids cannot be recycled while the entry lives."""
    if isinstance(p, pragma.ParallelRegion):
        return ("region", tuple(_program_signature(s) for s in p.stages))
    if isinstance(p, pragma.SerialStage):
        return ("serial", id(p.fn), p.reads)
    return ("for", id(p.body), p.bounds, p.collapse,
            (p.schedule.kind, p.schedule.chunk),
            tuple(sorted(p.reduction.items())))


def _env_signature(env: Mapping[str, Any]) -> tuple:
    """Shape/dtype identity of the environment, derived host-side.

    This runs on every cache probe, so it must not touch the device:
    the historical ``jnp.asarray`` fallback device-put every non-array
    env value (python scalars, lists) on the hot key path.  Python
    values type through numpy + ``canonicalize_dtype`` instead, which
    lands on the same dtype ``jnp.asarray`` would have (x64 off:
    float → float32, int → int32) without materializing anything."""
    sig = []
    for k in sorted(env):
        v = env[k]
        shape = getattr(v, "shape", None)
        dtype = getattr(v, "dtype", None)
        if shape is None or dtype is None:
            arr = np.asarray(v)
            shape = arr.shape
            dtype = jax.dtypes.canonicalize_dtype(arr.dtype)
        sig.append((k, tuple(shape), str(dtype)))
    return tuple(sig)


def _stable_program_token(p) -> tuple:
    """Cross-process analogue of :func:`_program_signature` for the
    persistent store: loop bodies hash by bytecode + consts + closure
    values (:func:`repro.core.aot_store.fingerprint`) instead of by
    ``id()``, so the same source program keys identically in every
    process."""
    if isinstance(p, pragma.ParallelRegion):
        return ("region",
                tuple(_stable_program_token(s) for s in p.stages))
    if isinstance(p, pragma.SerialStage):
        return ("serial", aot_store_mod.fingerprint(p.fn), p.reads)
    return ("for", aot_store_mod.fingerprint(p.body), p.bounds, p.collapse,
            (p.schedule.kind, p.schedule.chunk),
            tuple(sorted(p.reduction.items())))


def _mesh_signature(mesh) -> tuple:
    return tuple((str(a), int(mesh.shape[a])) for a in mesh.axis_names)


# ---------------------------------------------------------------------------
# compile()
# ---------------------------------------------------------------------------


def compile(
    program,
    mesh,
    options: Options | None = None,
    *,
    env_like: Mapping[str, Any] | None = None,
    **overrides,
) -> "Compiled":
    """Compile a :class:`~repro.core.pragma.ParallelFor` or
    :class:`~repro.core.pragma.ParallelRegion` to a distributed program.

    ``options`` carries every knob; as a convenience the fields may be
    given as keyword overrides instead (``omp.compile(p, mesh,
    lowering="master_worker")``).  ``env_like`` (shapes only) runs the
    pass pipeline eagerly; without it the pipeline runs on first call,
    when the environment shapes are known.

    Returns a :class:`Compiled` artifact: callable, ``.run(env)``,
    ``.plan`` / ``.boundaries`` / ``.passes`` / ``.report()`` /
    ``.cost_summary()``.
    """
    from repro.core import transform as tf

    if options is None:
        options = Options(**overrides)
    elif overrides:
        raise CompileError(
            "pass either an Options object or keyword overrides, not both "
            f"(got Options plus {sorted(overrides)})")
    if not isinstance(options, Options):
        raise CompileError(
            f"options must be an omp.Options, got {type(options).__name__}")
    if not isinstance(program, (pragma.ParallelFor, pragma.ParallelRegion)):
        raise CompileError(
            "omp.compile expects a ParallelFor or ParallelRegion, got "
            f"{type(program).__name__}")

    axis, num = tf.resolve_axes(program, mesh, options.axis)
    _validate_combination(program, options, num, mesh)
    compiled = Compiled(program=program, mesh=mesh, options=options,
                        axis=axis, num_devices=num)
    if env_like is not None:
        compiled._ensure(env_like)
    return compiled


def _on_tpu(mesh) -> bool:
    return mesh.devices.flat[0].platform == "tpu"


def _validate_combination(program, options: Options, num, mesh) -> None:
    """Cross-field validation that needs the program: one diagnostics
    path instead of ad-hoc raises scattered through the lowerings."""
    rank = program.rank
    if options.pallas_interpret and _on_tpu(mesh):
        raise CompileError(
            "Options.pallas_interpret=True on a TPU mesh: Pallas spans "
            "run compiled on the chip, never in interpret mode.  Drop "
            "pallas_interpret.")
    if options.lowering is Lowering.MASTER_WORKER:
        if rank == 2:
            raise CompileError(
                "Lowering.MASTER_WORKER × collapse=2: the paper's "
                "master/worker staging is rank-1 only.  Use "
                "Lowering.FUSED (default) or Lowering.COLLECTIVE for "
                "rank-2 nests.")
        if options.shard is ShardPolicy.SLICE:
            raise CompileError(
                "ShardPolicy.SLICE has no effect under "
                "Lowering.MASTER_WORKER (the master always sends full "
                "buffers, paper Fig. 1b); use Lowering.COLLECTIVE for "
                "sliced inputs.")
        if num < 2:
            raise CompileError(
                "Lowering.MASTER_WORKER needs >= 2 mesh ranks (rank 0 is "
                f"the master); this mesh has {num}.")

    cw = options.chunk_weights
    if cw is not None:
        if isinstance(program, pragma.ParallelRegion) \
                and options.lowering is Lowering.FUSED:
            raise CompileError(
                "Options.chunk_weights on a region requires "
                "Lowering.COLLECTIVE (per-loop staging): the fused "
                "region executor's ring halo exchanges and slab "
                "residency assume cyclic chunk ownership.")
        nested = any(e is None or isinstance(e, tuple) for e in cw)
        if rank == 2:
            if not nested:
                raise CompileError(
                    "collapse=2 needs per-axis chunk_weights: a 2-tuple "
                    "of weight vectors (or None to keep an axis "
                    f"cyclic), got {cw!r}")
            for d, (e, p_d) in enumerate(zip(cw, num)):
                if e is not None and len(e) != p_d:
                    raise CompileError(
                        f"chunk_weights[{d}] has {len(e)} entries but "
                        f"mesh axis {d} has {p_d} devices")
        else:
            if nested:
                raise CompileError(
                    "rank-1 loops need a flat per-device chunk_weights "
                    f"vector, got the per-axis form {cw!r}")
            if len(cw) != num:
                raise CompileError(
                    f"chunk_weights has {len(cw)} entries but the mesh "
                    f"axis has {num} devices")


# ---------------------------------------------------------------------------
# Pipeline execution
# ---------------------------------------------------------------------------


def _lowering_str(options: Options) -> str:
    return ("master_worker" if options.lowering is Lowering.MASTER_WORKER
            else "collective")


def _build_artifacts(program, env_like, num, axis, options) -> _Artifacts:
    """Run the passes before ``lower``, each record with its seconds."""
    env_shapes = {k: _aval_of(v) for k, v in env_like.items()}
    if isinstance(program, pragma.ParallelRegion):
        if options.lowering in (Lowering.FUSED, Lowering.PALLAS):
            build = _build_region_fused
        else:
            build = _build_region_staged
    else:
        build = _build_block
    with timing.PassClock() as clock:
        art = build(program, env_shapes, num, axis, options)
    art.passes = tuple(
        dataclasses.replace(pr, seconds=clock.seconds.get(pr.name, 0.0))
        for pr in art.passes)
    return art


def _pallas_pass(options: Options, kernel_plan) -> tuple:
    """The extra **pallas** PassRecord (only under Lowering.PALLAS, so
    the default 6-pass chain stays pinned)."""
    if options.lowering is not Lowering.PALLAS:
        return ()
    return (PassRecord(
        "pallas",
        input="exchange-free compute spans + chunk geometry "
              "(tile derivation per axis)",
        output=kernel_plan),)


def _build_block(program, env_shapes, num, axis, options) -> _Artifacts:
    low = _lowering_str(options)
    # a Pallas kernel serves x[i]-style reads from chunk windows only (a
    # read of a replicated buffer would be a gather), so Pallas blocks
    # slice their inputs as fused regions always do
    shard_inputs = (options.shard is ShardPolicy.SLICE
                    or options.lowering is Lowering.PALLAS)
    nest, ctx = plan_mod.analyze_program(program, env_shapes)
    chunks_axes = plan_mod.plan_schedule(
        program, nest, num, lowering=low,
        paper_master_excluded=options.paper_master_excluded,
        schedule=options.schedule, weights=options.chunk_weights)
    plan = plan_mod.decide_strategies(
        program, nest, ctx, chunks_axes, axis=axis, lowering=low,
        shard_inputs=shard_inputs)
    passes = (
        PassRecord("analyze",
                   input=f"block {program.name!r} + env shapes",
                   output=(nest, ctx)),
        PassRecord("schedule",
                   input="loop nest + schedule clause(s)",
                   output=chunks_axes),
        PassRecord("plan",
                   input="context + chunk plans",
                   output=plan),
        PassRecord("plan_comm",
                   input="single block: no inter-loop slab boundaries",
                   output=()),
        PassRecord("schedule_comm",
                   input="single block: no region-wide exchanges to "
                         "schedule (per-block combines fuse at lower)",
                   output=()),
    )
    if options.lowering is Lowering.PALLAS:
        from repro.core import pallas_lower as plx

        passes = passes + _pallas_pass(
            options, plx.plan_block_kernel(plan, name=program.name))
    return _Artifacts(passes=passes, exe_plan=plan, program=program)


def _build_region_fused(region, env_shapes, num, axis,
                        options) -> _Artifacts:
    from repro.core import comm_schedule as cs_mod
    from repro.core import region as region_mod

    try:
        rp = region_mod.plan_region(
            region, env_shapes, num, axis=axis, comm=options.comm.value,
            schedule=options.schedule)
    except LoopNotCanonical:
        raise
    except Exception as e:
        if options.lowering is Lowering.PALLAS:
            # almost always a host-side serial glue stage that cannot be
            # shape-traced — the pallas lowering runs everything inside
            # one shard_map and cannot leave the device for glue
            raise CompileError(
                f"Lowering.PALLAS cannot compile region {region.name!r}: "
                f"a stage is not shape-traceable "
                f"({type(e).__name__}: {e}).  Host-side serial glue "
                "(numpy conversion, I/O) runs only under the staged "
                "Lowering.COLLECTIVE path.") from e
        raise
    rp.comm_sched = cs_mod.build_comm_schedule(
        rp, mode=options.comm_schedule)
    loop_stages = [se for se in rp.stages if se.plan is not None]
    passes = (
        PassRecord("analyze",
                   input=f"region {region.name!r} "
                         f"({len(region.stages)} stages) + env shapes",
                   output=tuple((se.name, se.plan.context)
                                for se in loop_stages)),
        PassRecord("schedule",
                   input="per-stage loop nests + schedule clause(s)",
                   output=tuple((se.name, se.plan.chunks_axes)
                                for se in loop_stages)),
        PassRecord("plan",
                   input="per-stage contexts + chunk plans "
                         "(inter-loop residency planner)",
                   output=rp),
        PassRecord("plan_comm",
                   input="stage OUT layouts vs next-stage IN needs",
                   output=tuple(rp.comms)),
        PassRecord("schedule_comm",
                   input="planned boundary exchanges + stage order "
                         "(aggregate payloads / fuse combines / hoist "
                         "to producers)",
                   output=rp.comm_sched),
    )
    if options.lowering is Lowering.PALLAS:
        from repro.core import pallas_lower as plx

        passes = passes + _pallas_pass(
            options, plx.plan_region_kernels(rp))
    return _Artifacts(passes=passes, exe_plan=rp, program=region)


def _build_region_staged(region, env_shapes, num, axis,
                         options) -> _Artifacts:
    """Per-loop staging (COLLECTIVE / MASTER_WORKER on a region): each
    loop planned in isolation, environment shapes threaded through the
    stages the way the staged executor will see them.

    Serial glue is shape-traced (``jax.eval_shape``) to thread its
    output shapes.  Unlike the fused lowering — which *executes* glue
    inside the shard_map and therefore requires traceable glue — the
    staged executor runs glue eagerly on concrete arrays, so host-side
    glue (numpy conversion, I/O) is legal here: when its shapes cannot
    be traced, planning of the remaining stages is deferred to run time
    (the historical per-call behavior) instead of failing the compile."""
    low = _lowering_str(options)
    shard_inputs = options.shard is ShardPolicy.SLICE
    shapes = dict(env_shapes)
    analyses, schedules, plans = [], [], []
    deferred = None
    for stage in region.stages:
        if isinstance(stage, pragma.SerialStage):
            try:
                with timing.timed_pass("analyze"):
                    out_sh = jax.eval_shape(stage.fn, shapes)
            except Exception as e:  # host-side glue: shapes unknowable
                deferred = (f"serial stage {stage.name!r} is not "
                            f"shape-traceable ({type(e).__name__}); "
                            "remaining stages plan at run time")
                break
            for k, v in out_sh.items():
                shapes[k] = jax.ShapeDtypeStruct(v.shape, v.dtype)
            continue
        nest, ctx = plan_mod.analyze_program(stage, shapes)
        chunks_axes = plan_mod.plan_schedule(
            stage, nest, num, lowering=low,
            paper_master_excluded=options.paper_master_excluded,
            schedule=options.schedule, weights=options.chunk_weights)
        p = plan_mod.decide_strategies(
            stage, nest, ctx, chunks_axes, axis=axis, lowering=low,
            shard_inputs=shard_inputs)
        analyses.append((stage.name, ctx))
        schedules.append((stage.name, chunks_axes))
        plans.append((stage.name, p))
        for key, dec in p.vars.items():
            if dec.out_strategy == "reduce" and key not in shapes:
                info = p.context.vars[key]
                shapes[key] = jax.ShapeDtypeStruct(
                    info.write.value_shape, info.write.value_dtype)
    stage_plans = tuple(plans)
    plan_input = ("per-stage contexts + chunk plans "
                  "(each loop planned in isolation)")
    if deferred is not None:
        plan_input += f"; {deferred}"
    passes = (
        PassRecord("analyze",
                   input=f"region {region.name!r} "
                         f"({len(region.stages)} stages) + env shapes",
                   output=tuple(analyses)),
        PassRecord("schedule",
                   input="per-stage loop nests + schedule clause(s)",
                   output=tuple(schedules)),
        PassRecord("plan",
                   input=plan_input,
                   output=stage_plans),
        PassRecord("plan_comm",
                   input="staged lowering: every boundary round-trips "
                         "through the replicated layout (paper Fig. 1b)",
                   output=()),
        PassRecord("schedule_comm",
                   input="staged lowering: no region-wide exchanges to "
                         "schedule (per-block combines fuse at lower)",
                   output=()),
    )
    return _Artifacts(
        passes=passes,
        # a partial plan list cannot feed the executor 1:1 — fall back
        # to the historical per-call planning for the whole region
        exe_plan=None if deferred is not None else stage_plans,
        program=region)


def _make_executor(program, mesh, axis, options: Options, exe_plan):
    """The **lower** pass: bind the planned artifacts to the mesh."""
    from repro.core import region as region_mod
    from repro.core import transform as tf

    use_pallas = options.lowering is Lowering.PALLAS
    if isinstance(program, pragma.ParallelRegion):
        fused = options.lowering in (Lowering.FUSED, Lowering.PALLAS)
        return region_mod.DistributedRegion(
            region=program, mesh=mesh,
            plan=exe_plan if fused else None,
            axis=axis, lowering=_lowering_str(options), fuse=fused,
            shard_inputs=options.shard is ShardPolicy.SLICE,
            unroll_chunks=options.unroll_chunks,
            paper_master_excluded=options.paper_master_excluded,
            comm=options.comm.value,
            comm_schedule=options.comm_schedule,
            schedule_override=options.schedule,
            stage_plans=None if fused else exe_plan,
            use_pallas=use_pallas,
            pallas_interpret=options.pallas_interpret,
            chunk_weights=options.chunk_weights)
    return tf.DistributedProgram(
        program=program, mesh=mesh, plan=exe_plan, axis=axis,
        lowering=_lowering_str(options),
        shard_inputs=options.shard is ShardPolicy.SLICE,
        unroll_chunks=options.unroll_chunks,
        paper_master_excluded=options.paper_master_excluded,
        schedule_override=options.schedule,
        comm_schedule=options.comm_schedule,
        use_pallas=use_pallas,
        pallas_interpret=options.pallas_interpret,
        chunk_weights=options.chunk_weights)


def _sig_avals(sig: tuple) -> dict:
    return {k: jax.ShapeDtypeStruct(
                tuple(sh), jax.dtypes.canonicalize_dtype(np.dtype(dt)))
            for k, sh, dt in sig}


def _export_and_save(dkey: str, exe, sig: tuple):
    """AOT-lower the executor end-to-end (jit → lower → XLA compile)
    and persist the serialized executable under ``dkey``.  Returns the
    compiled executable — which also serves this process's calls — or
    ``None`` when the program cannot be staged out (e.g. host-side
    serial glue in a staged region): those fall back to the per-call
    jit path, exactly as before persistence existed."""
    try:
        compiled = jax.jit(lambda env: dict(exe(env))).lower(
            _sig_avals(sig)).compile()
    except Exception:
        return None
    _PERSISTENT.save(dkey, compiled)
    return compiled


if os.environ.get(aot_store_mod.ENV_VAR):
    enable_persistent_cache()


# ---------------------------------------------------------------------------
# The Compiled artifact
# ---------------------------------------------------------------------------

#: Fault-injection hook (repro.runtime.fault_injection installs a
#: callable here inside ``inject()``).  Called as ``hook("run")`` at
#: every ``Compiled.run`` entry and ``hook("run_exit", out)`` on exit
#: (the return value replaces ``out`` — output corruption faults).
#: ``None`` in production: the cost when inactive is one attribute
#: check per call.
_fault_hook = None


@dataclasses.dataclass
class Compiled:
    """The unified compilation artifact for blocks and regions.

    Callable (``compiled(env)`` / ``compiled.run(env)``) like the
    programs it replaces; additionally exposes the staged pipeline:

    * ``.passes``       — the analyze→lower :class:`PassRecord` chain
      (``analyze → schedule → plan → plan_comm → schedule_comm →
      lower``),
    * ``.plan``         — the planning artifact (:class:`DistPlan`,
      :class:`~repro.core.region.RegionPlan`, or per-stage plans for
      staged regions),
    * ``.boundaries``   — the planned
      :class:`~repro.core.comm.BoundaryComm` list (fused regions),
    * ``.report()``     — the rendered "generated MPI code" view,
    * ``.cost_summary()`` — modeled communication totals as a dict,
    * ``.cache_hit``    — whether the last build came from the cache,
    * ``.executor_runs`` / ``.executor_seconds`` — entries into the
      executor from :meth:`run`, and their wall seconds,
    * ``.chunk_eval_sliced`` / ``.chunk_eval_scan`` — lax stages
      those entries traced on the sliced chunk evaluator / on the
      chunk scan,
    * ``.indirect_reads`` / ``.indirect_elems`` — stages those entries
      traced with a read through an index array, and the index entries
      they look up per call.

    The pipeline needs environment *shapes*; compile with ``env_like=``
    to run it eagerly, otherwise it runs (through the compilation
    cache) on first call.  A call with different env shapes re-plans —
    and re-consults the cache — automatically.
    """

    program: Any
    mesh: Any
    options: Options
    axis: Any
    num_devices: Any
    cache_hit: bool | None = None
    executor_runs: int = dataclasses.field(default=0, compare=False)
    """Entries into the executor from :meth:`run`: under ``jax.jit``
    one per trace, and one per bare (eager) call, each of which
    compiles again."""
    executor_seconds: float = dataclasses.field(default=0.0, compare=False)
    """Wall seconds of those entries (host span ``omp.executor``)."""
    chunk_eval_sliced: int = dataclasses.field(default=0, compare=False)
    """Lax stages (rank 1 or 2) those entries evaluated over the whole
    local chunk stack, window reads served as slices."""
    chunk_eval_scan: int = dataclasses.field(default=0, compare=False)
    """Lax stages those entries ran as a scan of vmapped chunks (a
    window read the sliced evaluator cannot serve)."""
    indirect_reads: int = dataclasses.field(default=0, compare=False)
    """Stages those entries traced with a read through an index array
    (``x[idx[i]]``, device scope ``omp.indirect.<keys>``)."""
    indirect_elems: int = dataclasses.field(default=0, compare=False)
    """Index entries those stages look up per call, summed over the
    entries."""
    _exe: Any = dataclasses.field(default=None, repr=False)
    _passes: tuple | None = dataclasses.field(default=None, repr=False)
    _env_sig: tuple | None = dataclasses.field(default=None, repr=False)
    # Persistent-store state: the AOT-compiled end-to-end executable
    # (serves run() without re-tracing), and — after a disk restore
    # that skipped planning — the env avals to rebuild the pass
    # artifacts lazily on inspection.
    _runner: Any = dataclasses.field(default=None, repr=False)
    _restored_env: Any = dataclasses.field(default=None, repr=False)

    # -- execution ---------------------------------------------------------

    def run(self, env: Mapping[str, Any]) -> dict:
        if _fault_hook is not None:
            _fault_hook("run")
        out = None
        self._ensure(env)
        if self._runner is not None:
            try:
                out = dict(self._runner(env))
            except Exception:
                # The persisted executable refused these inputs (aval /
                # layout / backend skew).  The store must never turn
                # into a crash: drop the runner and fall back to the
                # planned executor.
                self._runner = None
        if out is None:
            if self._exe is None:
                self._ensure(env, allow_restore=False)
            t0 = time.perf_counter()
            tally = {"sliced": 0, "scan": 0, "indirect_reads": 0,
                     "indirect_elems": 0}
            try:
                with TraceAnnotation("omp.executor"), \
                        timing.chunk_tally(tally):
                    out = self._exe(env)
            finally:
                seconds = time.perf_counter() - t0
                self.executor_runs += 1
                self.executor_seconds += seconds
                self.chunk_eval_sliced += tally["sliced"]
                self.chunk_eval_scan += tally["scan"]
                self.indirect_reads += tally["indirect_reads"]
                self.indirect_elems += tally["indirect_elems"]
                timing.add_executor(seconds)
        if _fault_hook is not None:
            out = _fault_hook("run_exit", out)
        return out

    __call__ = run

    @property
    def restored(self) -> bool:
        """Whether this artifact was served from the persistent store
        (planning skipped; pass artifacts rebuild lazily on access)."""
        return self._restored_env is not None

    # -- pipeline ----------------------------------------------------------

    def _ensure(self, env_like: Mapping[str, Any], *,
                allow_restore: bool = True) -> None:
        sig = _env_signature(env_like)
        if sig == self._env_sig:
            if self._exe is not None:
                return
            if allow_restore and self._runner is not None:
                return
        key = (_program_signature(self.program), _mesh_signature(self.mesh),
               self.options, sig)
        entry = _CACHE.get(key)          # warm hits: lock-free
        if entry is not None:
            _HITS.inc()
            entry.stamp = next(_TICK)
            self.cache_hit = True
            self._bind(entry.art, sig)
            if _PERSISTENT is not None:
                self._runner = _EXE_CACHE.get(self._disk_key(sig))
            return
        if (allow_restore and _PERSISTENT is not None
                and self._try_restore(sig)):
            return
        _MISSES.inc()                    # miss path: build, then lock
        self.cache_hit = False
        art = _build_artifacts(self.program, env_like, self.num_devices,
                               self.axis, self.options)
        with _CACHE_LOCK:
            _CACHE[key] = _Entry(art, next(_TICK))
            while len(_CACHE) > _CACHE_CAP:
                oldest = min(_CACHE, key=lambda k: _CACHE[k].stamp)
                del _CACHE[oldest]
        self._bind(art, sig)
        if _PERSISTENT is not None:
            dkey = self._disk_key(sig)
            runner = _EXE_CACHE.get(dkey)
            if runner is None and dkey not in _UNEXPORTABLE:
                runner = _export_and_save(dkey, self._exe, sig)
                if runner is None:
                    _UNEXPORTABLE.add(dkey)
                else:
                    _EXE_CACHE[dkey] = runner
            self._runner = runner

    def _bind(self, art: _Artifacts, sig: tuple) -> None:
        with timing.PassClock() as clock, timing.timed_pass("lower"):
            exe = _make_executor(self.program, self.mesh, self.axis,
                                 self.options, art.exe_plan)
            self._passes = art.passes
            self._exe = exe
            self._env_sig = sig
            self._runner = None
            if self.options.lowering is Lowering.PALLAS \
                    and _on_tpu(self.mesh):
                # tracing compiles every span for the chip: a span Mosaic
                # refuses raises CompileError here, not at the first call
                jax.eval_shape(lambda env: dict(exe(env)), _sig_avals(sig))
        self._passes = art.passes + (PassRecord(
            "lower", input="planned artifacts + mesh", output=exe,
            seconds=clock.seconds["lower"]),)

    def _disk_key(self, sig: tuple) -> str:
        return aot_store_mod.fingerprint(
            "compiled-run", aot_store_mod.STORE_VERSION,
            _stable_program_token(self.program),
            _mesh_signature(self.mesh), self.options, self.axis, sig)

    def _try_restore(self, sig: tuple) -> bool:
        """Serve this compile from the persistent store: planning is
        skipped entirely — the pass artifacts rebuild lazily
        (deterministically) if inspected."""
        dkey = self._disk_key(sig)
        runner = _EXE_CACHE.get(dkey)
        if runner is None:
            if dkey in _UNEXPORTABLE:
                return False
            runner = _PERSISTENT.load(dkey)
            if runner is None:
                return False
            _EXE_CACHE[dkey] = runner
        self.cache_hit = True
        self._runner = runner
        self._exe = None
        self._passes = None
        self._env_sig = sig
        self._restored_env = {k: jax.ShapeDtypeStruct(tuple(sh), np.dtype(dt))
                              for k, sh, dt in sig}
        return True

    def _built(self) -> None:
        if self._passes is None and self._restored_env is not None:
            runner = self._runner
            self._ensure(self._restored_env, allow_restore=False)
            self._runner = runner
        if self._passes is None:
            raise CompileError(
                "the pass pipeline has not run yet: call the compiled "
                "program (or compile with env_like=) to build the plan "
                "before inspecting it")

    @property
    def passes(self) -> tuple:
        """The recorded ``analyze → schedule → plan → plan_comm →
        schedule_comm → lower`` :class:`PassRecord` chain."""
        self._built()
        return self._passes

    def _pass(self, name: str) -> PassRecord:
        self._built()
        for pr in self._passes:
            if pr.name == name:
                return pr
        raise KeyError(name)

    @property
    def plan(self):
        """The planning artifact: a :class:`~repro.core.plan.DistPlan`
        for a block, a :class:`~repro.core.region.RegionPlan` for a
        fused region, per-stage ``(name, DistPlan)`` pairs for a staged
        region."""
        return self._pass("plan").output

    @property
    def boundaries(self) -> tuple:
        """The planned boundary exchanges (empty for single blocks and
        staged regions — nothing crosses a fused boundary there)."""
        return self._pass("plan_comm").output

    @property
    def comm_schedule(self):
        """The **schedule_comm** artifact: a
        :class:`~repro.core.comm_schedule.CommSchedule` for fused
        regions (aggregation groups, fused combines, launch accounting);
        ``()`` for single blocks and staged regions."""
        return self._pass("schedule_comm").output

    @property
    def kernel_plan(self):
        """The **pallas** artifact
        (:class:`~repro.core.pallas_lower.KernelPlan`: tile geometry +
        fusion spans) under ``Lowering.PALLAS``; ``None`` otherwise."""
        self._built()
        for pr in self._passes:
            if pr.name == "pallas":
                return pr.output
        return None

    # -- reporting ---------------------------------------------------------

    def report(self) -> str:
        from repro.core import report as report_mod

        self._built()
        return report_mod.render_compiled(self)

    def cost_summary(self) -> dict:
        """Modeled communication totals of the chosen plan."""
        from repro.core import region as region_mod
        from repro.core import report as report_mod

        plan = self.plan
        base = {"lowering": self.options.lowering.value}
        if isinstance(plan, region_mod.RegionPlan):
            out = {
                "kind": "region", **base,
                "comm": plan.comm_mode,
                "planned_wire_bytes": plan.planned_wire_bytes,
                "gather_wire_bytes": plan.gather_wire_bytes,
                "n_elided": plan.n_elided,
                "n_halo": plan.n_halo,
                "n_reshards": plan.n_reshards,
            }
            sched = plan.comm_sched
            if sched is not None:
                out["comm_schedule"] = sched.mode
                out["launches_inline"] = sched.launches_inline
                out["launches_scheduled"] = sched.launches_scheduled
                out["n_hoisted"] = sched.n_hoisted
            return out
        if isinstance(plan, plan_mod.DistPlan):
            _, total = report_mod._comm_breakdown(plan)
            return {"kind": "block", **base, "modeled_bytes": total}
        total = sum(report_mod._comm_breakdown(p)[1] for _, p in plan)
        return {"kind": "region_staged", **base, "modeled_bytes": total,
                "n_loops": len(plan)}
