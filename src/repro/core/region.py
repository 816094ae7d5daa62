"""Whole-program OMP→MPI transformation with inter-loop residency planning.

OMP2MPI transforms each ``parallel for`` in isolation: every block stages
its IN buffers out of rank 0's shared memory and returns every OUT slab
back to it (paper Fig. 1b).  For a *chain* of blocks that means a
gather→rebroadcast round trip between consecutive loops even when the
next loop immediately re-distributes the same array the same way — the
communication bottleneck follow-up systems (OMP2HMPP, MPI-rical) attack
by reasoning across statement boundaries.

This module transforms a :class:`~repro.core.pragma.ParallelRegion` as a
whole:

* :func:`plan_region` — the **inter-loop residency planner**.  It walks
  the stage sequence, tracking the layout of every environment buffer
  (``replicated`` or chunk-cyclic ``slab``), and matches each loop's OUT
  layout (from its :class:`~repro.core.plan.DistPlan`) against the next
  loop's IN requirement:

  - compatible layouts → the buffer **stays resident** in its slab; the
    gather→rebroadcast round trip is elided entirely;
  - incompatible layouts → a single minimal resharding collective (an
    ``all_gather``) materialises the buffer, replacing the staged
    master round trip;
  - serial glue stages run redundantly on every rank over replicated
    buffers (only their declared reads are materialised).

* :class:`DistributedRegion` — the executor
  (:func:`repro.core.api.compile` is the entry point; the historical
  :func:`region_to_mpi` remains as a deprecation shim).
  ``Lowering.FUSED`` fuses the whole region into **one** ``shard_map``
  so resident buffers never leave their device; ``MASTER_WORKER`` (and
  per-loop ``COLLECTIVE``) keep the paper's per-loop staging as the
  measurable baseline (EXPERIMENTS.md §Perf-C).

Boundary lowering is delegated to the cost-modeled communication
planner (:mod:`repro.core.comm`): each slab→consumer handoff becomes
the cheapest of ``resident`` / ``halo`` (neighbor ``ppermute`` ring
shifts) / ``all_gather`` / ``replicate``, recorded as a
:class:`~repro.core.comm.BoundaryComm` on the plan.  ``comm="gather"``
disables the halo strategy — the PR 1 baseline, kept measurable
(EXPERIMENTS.md §Perf-D).

Residency compatibility (the layout-matching rule): loop A's write slab
holds row ``base + j*c + r`` at (chunk ``j``, lane ``r``); loop B can
consume it in place iff both loops share the chunk geometry
``(c, P, n_loc, padded)``, cover the same trip count, and B's per-
iteration read map equals A's write map (``x[k + base]`` both sides —
identity or aligned unit-stride).  Strided, stencil and whole-array reads
fall back to the resharding collective.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.compat import shard_map
from repro.core import comm as comm_mod
from repro.core import nest as nest_mod
from repro.core import pragma, reduction as red_mod
from repro.core import transform as tf
from repro.core.context import _aval_of
from repro.core.comm import (  # noqa: F401 (re-export)
    BoundaryComm,
    SlabLayout,
    SlabLayout2,
)
from repro.core.loop import LoopNotCanonical
from repro.core.plan import DistPlan, make_plan
from repro.core.tensor_plan import slab_spec
from repro.core.timing import timed_pass

REPLICATED = "repl"

_SLABS = (SlabLayout, SlabLayout2)


@dataclasses.dataclass
class StageExec:
    """One stage of the fused execution schedule."""

    name: str
    kind: str                          # "loop" | "serial"
    stage: Any                         # ParallelFor | SerialStage
    plan: DistPlan | None
    gathers: tuple[str, ...]           # keys resharded (materialised) first
    feeds: dict[str, str]              # sharded-in key -> "resident"|"slice"
    serial_writes: tuple[str, ...] = ()


@dataclasses.dataclass
class RegionPlan:
    """Output of the inter-loop residency planner."""

    name: str
    axis: str
    num_devices: int
    stages: list[StageExec]
    env_keys: list[str]                # region input keys
    touched_keys: list[str]            # keys (re)written by some stage
    final_layout: dict[str, Any]       # touched key -> REPLICATED | SlabLayout
    n_elided: int                      # resident handoffs (round trips saved)
    n_reshards: int                    # minimal collectives inserted
    log: list[str]                     # human-readable transition journal
    comms: list[BoundaryComm] = dataclasses.field(default_factory=list)
    n_halo: int = 0                    # boundaries lowered to ppermute shifts
    comm_mode: str = "auto"
    rank: int = 1                      # nest rank shared by every loop
    # the schedule_comm artifact (repro.core.comm_schedule.CommSchedule):
    # aggregation groups + fused combines + launch accounting, attached
    # after planning by the compile pipeline (or lazily by the executor)
    comm_sched: Any = None

    @property
    def loop_plans(self) -> list[DistPlan]:
        return [s.plan for s in self.stages if s.plan is not None]

    @property
    def planned_wire_bytes(self) -> int:
        """Modeled wire bytes of the chosen boundary ops."""
        return sum(bc.cost.wire_bytes for bc in self.comms)

    @property
    def gather_wire_bytes(self) -> int:
        """Modeled wire bytes under the PR 1 rule (residency kept, every
        non-resident boundary lowered to the gather)."""
        total = 0
        for bc in self.comms:
            if bc.op == comm_mod.RESIDENT:
                continue
            alts = [c for op, c in bc.alternatives.items()
                    if op in (comm_mod.ALL_GATHER, comm_mod.REPLICATE)]
            total += alts[0].wire_bytes if alts else bc.cost.wire_bytes
        return total


# ---------------------------------------------------------------------------
# The residency planner
# ---------------------------------------------------------------------------


def _boundary_replicated(stage_name, key, st, aval, comm, chunks=None):
    """Plan a forced-replication boundary for either slab rank."""
    if isinstance(st, SlabLayout2):
        return comm_mod.plan_boundary2(
            stage=stage_name, key=key, layout=st, chunks_axes=None,
            trips=(0, 0), aval=aval, in_strategy="none", halo_axes=None,
            shard_ndim=0, needs_replicated=True, mode=comm)
    return comm_mod.plan_boundary(
        stage=stage_name, key=key, layout=st, chunks=chunks, trip=0,
        aval=aval, in_strategy="none", halo=None, needs_replicated=True,
        mode=comm)


@timed_pass("plan")
def plan_region(
    region: pragma.ParallelRegion,
    env: Mapping[str, Any],
    num_devices: int | tuple,
    *,
    axis: str | tuple = "data",
    comm: str = "auto",
    schedule: pragma.Schedule | None = None,
) -> RegionPlan:
    """Match each loop's OUT layout against the next loop's IN needs,
    lowering each slab boundary through the cost-modeled communication
    planner (``comm="auto"``; ``comm="gather"`` pins the PR 1 all-gather
    baseline).  Rank-2 regions (every loop ``collapse=2``) plan over a
    2-D mesh: ``axis`` and ``num_devices`` are then 2-tuples."""
    if comm not in comm_mod.COMM_MODES:
        raise ValueError(
            f"unknown comm mode {comm!r}; expected {comm_mod.COMM_MODES}")
    rank = region.rank
    if (rank == 2) != isinstance(axis, tuple):
        raise LoopNotCanonical(
            f"region rank {rank} does not match mesh axis clause {axis!r} "
            "(collapse=2 regions need a 2-tuple of mesh axes)")
    env_shapes = {k: _aval_of(v) for k, v in env.items()}
    state: dict[str, Any] = {k: REPLICATED for k in env_shapes}
    touched: set[str] = set()
    stages: list[StageExec] = []
    n_elided = n_reshards = n_halo = 0
    log: list[str] = []
    comms: list[BoundaryComm] = []

    for stage in region.stages:
        if isinstance(stage, pragma.SerialStage):
            reads = (stage.reads if stage.reads is not None
                     else tuple(env_shapes))
            gathers = tuple(
                k for k in reads if isinstance(state.get(k), _SLABS))
            out_sh = jax.eval_shape(stage.fn, env_shapes)
            if not isinstance(out_sh, dict):
                raise LoopNotCanonical(
                    f"serial stage {stage.name!r} must return a dict of "
                    "whole-array updates"
                )
            for k in gathers:
                n_reshards += 1
                comms.append(_boundary_replicated(
                    stage.name, k, state[k], env_shapes[k], comm))
                log.append(f"{stage.name}: reshard {k!r} "
                           f"(~{comm_mod.full_bytes(env_shapes[k])} B all-gather; "
                           "serial glue reads it)")
                state[k] = REPLICATED
            for k, v in out_sh.items():
                env_shapes[k] = jax.ShapeDtypeStruct(v.shape, v.dtype)
                state[k] = REPLICATED
                touched.add(k)
            stages.append(StageExec(
                name=stage.name, kind="serial", stage=stage, plan=None,
                gathers=gathers, feeds={}, serial_writes=tuple(out_sh)))
            continue

        plan = make_plan(stage, env_shapes, num_devices, axis=axis,
                         lowering="collective", shard_inputs=True,
                         schedule=schedule)
        t = plan.nest.total_trip
        if t == 0:
            # Zero-trip loop: the executor only folds reduction
            # identities (mirroring single-block ``_execute``); no other
            # buffer moves, so no layout changes either.
            gathers0: list[str] = []
            for key, dec in plan.vars.items():
                if dec.out_strategy != "reduce":
                    continue
                if isinstance(state.get(key), _SLABS):
                    gathers0.append(key)
                    n_reshards += 1
                    comms.append(_boundary_replicated(
                        stage.name, key, state[key], env_shapes[key], comm,
                        chunks=plan.chunks))
                    log.append(
                        f"{stage.name}: reshard {key!r} "
                        f"(~{comm_mod.full_bytes(env_shapes[key])} B all-gather; "
                        "zero-trip reduction folds the prior value)")
                state[key] = REPLICATED
                touched.add(key)
                if key not in env_shapes:
                    info = plan.context.vars[key]
                    env_shapes[key] = jax.ShapeDtypeStruct(
                        info.write.value_shape, info.write.value_dtype)
            stages.append(StageExec(
                name=stage.name, kind="loop", stage=stage, plan=plan,
                gathers=tuple(gathers0), feeds={}))
            continue
        if plan.rank == 2:
            se, n_e, n_h, n_r = _plan_loop_stage2(
                stage, plan, state, touched, env_shapes, comms, log, comm)
            n_elided += n_e
            n_halo += n_h
            n_reshards += n_r
            stages.append(se)
            continue
        gathers: list[str] = []
        feeds: dict[str, str] = {}
        for key, dec in plan.vars.items():
            st = state.get(key, REPLICATED)
            is_slab = isinstance(st, SlabLayout)
            write_b = dec.write_map.b if dec.write_map is not None else None

            # Out-merges that consume the pre-stage value need it
            # replicated — except a partial write replacing a slab of the
            # identical interval, whose prior chains through.
            interval_same = (is_slab and dec.out_strategy == "partial"
                             and st.base == write_b and st.cover == t)
            prior_repl = (
                dec.out_strategy == "scatter"
                or (dec.out_strategy == "partial" and not interval_same)
                or (dec.out_strategy == "reduce" and key in state)
            )

            consumes = dec.in_strategy in ("shard", "shard_halo", "replicate")
            if is_slab and (prior_repl or consumes):
                bc = comm_mod.plan_boundary(
                    stage=stage.name, key=key, layout=st, chunks=plan.chunks,
                    trip=t, aval=env_shapes[key],
                    in_strategy=dec.in_strategy, halo=dec.halo,
                    needs_replicated=(prior_repl
                                      or dec.in_strategy == "replicate"),
                    mode=comm)
                comms.append(bc)
                if bc.op == comm_mod.RESIDENT:
                    feeds[key] = "resident"
                    n_elided += 1
                    log.append(
                        f"{stage.name}: {key!r} stays RESIDENT "
                        f"(elides ~{2 * comm_mod.full_bytes(env_shapes[key])} B "
                        "gather+redistribute round trip)")
                elif bc.op == comm_mod.HALO:
                    feeds[key] = "halo"
                    n_halo += 1
                    g = bc.alternatives[comm_mod.ALL_GATHER].wire_bytes
                    log.append(
                        f"{stage.name}: {key!r} HALO-EXCHANGED "
                        f"(shift {bc.shift}, {bc.cost.hops} ppermute hop(s), "
                        f"~{bc.cost.wire_bytes} B on the wire vs ~{g} B "
                        "all-gather)")
                else:
                    gathers.append(key)
                    n_reshards += 1
                    state[key] = REPLICATED
                    log.append(
                        f"{stage.name}: reshard {key!r} "
                        f"(~{comm_mod.full_bytes(env_shapes[key])} B all-gather; "
                        f"{bc.reason})")
                    if dec.in_strategy in ("shard", "shard_halo"):
                        feeds[key] = "slice"
            elif dec.in_strategy in ("shard", "shard_halo"):
                feeds[key] = "slice"

            if dec.out_strategy == "identity":
                state[key] = SlabLayout.of(plan, base=0, has_prior=False)
                touched.add(key)
            elif dec.out_strategy == "partial":
                state[key] = SlabLayout.of(plan, base=write_b, has_prior=True)
                touched.add(key)
            elif dec.out_strategy in ("scatter", "put", "reduce"):
                state[key] = REPLICATED
                touched.add(key)
                if key not in env_shapes:     # fresh reduction output
                    info = plan.context.vars[key]
                    env_shapes[key] = jax.ShapeDtypeStruct(
                        info.write.value_shape, info.write.value_dtype)

        stages.append(StageExec(
            name=stage.name, kind="loop", stage=stage, plan=plan,
            gathers=tuple(gathers), feeds=feeds))

    final_layout = {k: state[k] for k in sorted(touched)}
    return RegionPlan(
        name=region.name, axis=axis, num_devices=num_devices,
        stages=stages, env_keys=list(env.keys()),
        touched_keys=sorted(touched), final_layout=final_layout,
        n_elided=n_elided, n_reshards=n_reshards, log=log,
        comms=comms, n_halo=n_halo, comm_mode=comm, rank=rank,
    )


def _plan_loop_stage2(stage, plan, state, touched, env_shapes, comms, log,
                      comm):
    """Residency planning for one rank-2 loop stage: the 2-D analogue of
    the rank-1 key loop in :func:`plan_region` (per-axis bases/covers,
    boundaries through :func:`repro.core.comm.plan_boundary2`)."""
    trips = plan.nest.trip_counts
    n_elided = n_halo = n_reshards = 0
    gathers: list[str] = []
    feeds: dict[str, str] = {}
    for key, dec in plan.vars.items():
        st = state.get(key, REPLICATED)
        is_slab = isinstance(st, SlabLayout2)
        write_bases = (tuple(m.b for m in dec.write_maps)
                       if dec.write_maps is not None else None)

        # Out-merges that consume the pre-stage value need it replicated
        # — except a partial write replacing a slab of the identical
        # rectangle, whose prior chains through.
        interval_same = (is_slab and dec.out_strategy == "partial"
                         and st.bases == write_bases and st.covers == trips)
        prior_repl = (
            (dec.out_strategy == "partial" and not interval_same)
            or (dec.out_strategy == "reduce" and key in state)
        )

        consumes = dec.in_strategy in ("shard_halo", "replicate")
        if is_slab and (prior_repl or consumes):
            bc = comm_mod.plan_boundary2(
                stage=stage.name, key=key, layout=st,
                chunks_axes=plan.chunks_axes, trips=trips,
                aval=env_shapes[key], in_strategy=dec.in_strategy,
                halo_axes=dec.halo_axes, shard_ndim=dec.shard_ndim,
                needs_replicated=(prior_repl
                                  or dec.in_strategy == "replicate"),
                mode=comm)
            comms.append(bc)
            if bc.op == comm_mod.RESIDENT:
                feeds[key] = "resident"
                n_elided += 1
                log.append(
                    f"{stage.name}: {key!r} stays RESIDENT "
                    f"(elides ~{2 * comm_mod.full_bytes(env_shapes[key])} B "
                    "gather+redistribute round trip)")
            elif bc.op == comm_mod.HALO:
                feeds[key] = "halo"
                n_halo += 1
                g = bc.alternatives[comm_mod.ALL_GATHER].wire_bytes
                log.append(
                    f"{stage.name}: {key!r} HALO-EXCHANGED 2-D "
                    f"(shifts {bc.shift}, {bc.cost.hops} ppermute hop(s), "
                    f"~{bc.cost.wire_bytes} B on the wire vs ~{g} B "
                    "all-gather)")
            else:
                gathers.append(key)
                n_reshards += 1
                state[key] = REPLICATED
                log.append(
                    f"{stage.name}: reshard {key!r} "
                    f"(~{comm_mod.full_bytes(env_shapes[key])} B all-gather; "
                    f"{bc.reason})")
                if dec.in_strategy == "shard_halo":
                    feeds[key] = "slice"
        elif dec.in_strategy == "shard_halo":
            feeds[key] = "slice"

        if dec.out_strategy == "identity":
            state[key] = SlabLayout2.of(plan, bases=(0, 0), has_prior=False)
            touched.add(key)
        elif dec.out_strategy == "partial":
            state[key] = SlabLayout2.of(plan, bases=write_bases,
                                        has_prior=True)
            touched.add(key)
        elif dec.out_strategy == "reduce":
            state[key] = REPLICATED
            touched.add(key)
            if key not in env_shapes:     # fresh reduction output
                info = plan.context.vars[key]
                env_shapes[key] = jax.ShapeDtypeStruct(
                    info.write.value_shape, info.write.value_dtype)

    se = StageExec(name=stage.name, kind="loop", stage=stage, plan=plan,
                   gathers=tuple(gathers), feeds=feeds)
    return se, n_elided, n_halo, n_reshards


# ---------------------------------------------------------------------------
# Distributed region program
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DistributedRegion:
    """The generated whole-program "MPI" code for a parallel region."""

    region: pragma.ParallelRegion
    mesh: Mesh
    plan: RegionPlan | None
    axis: str = "data"
    lowering: str = "collective"
    fuse: bool = True
    shard_inputs: bool = False          # per-loop fallback path only
    unroll_chunks: bool = False
    paper_master_excluded: bool | None = None
    comm: str = "auto"                  # boundary planner mode
    comm_schedule: str = "aggregate"    # schedule_comm mode
    schedule_override: pragma.Schedule | None = None
    stage_plans: tuple | None = None    # staged path: per-loop (name, plan)
    use_pallas: bool = False            # Lowering.PALLAS: tiled kernels
    pallas_interpret: bool | None = None
    chunk_weights: tuple | None = None  # straggler-weighted (staged only)

    def __call__(self, env: Mapping[str, Any]) -> dict[str, Any]:
        from repro.core import comm_schedule as cs_mod

        env = {k: jnp.asarray(v) for k, v in env.items()}
        with jax.named_scope(f"omp.region.{self.region.name}"):
            if self.lowering != "collective" or not self.fuse:
                return self._run_staged(env)
            if self.plan is None:
                self.plan = plan_region(
                    self.region, env,
                    tf.mesh_axis_sizes(self.mesh, self.axis),
                    axis=self.axis, comm=self.comm,
                    schedule=self.schedule_override)
            if self.plan.comm_sched is None:
                self.plan.comm_sched = cs_mod.build_comm_schedule(
                    self.plan, mode=self.comm_schedule)
            return _execute_region(self, env)

    def _run_staged(self, env: dict) -> dict:
        """Paper-faithful baseline: each loop transformed in isolation
        (data returns to replicated form between stages).  When the
        compile pipeline pre-planned the stages (``stage_plans``), those
        exact plans execute — no re-planning per call."""
        out = dict(env)
        plans = iter(self.stage_plans) if self.stage_plans is not None \
            else None
        for stage in self.region.stages:
            if isinstance(stage, pragma.SerialStage):
                with jax.named_scope(f"omp.stage.{stage.name}"):
                    out = stage(out)
                continue
            plan = None
            if plans is not None:
                _, plan = next(plans)
            out = tf.DistributedProgram(
                program=stage, mesh=self.mesh, plan=plan, axis=self.axis,
                lowering=self.lowering, shard_inputs=self.shard_inputs,
                unroll_chunks=self.unroll_chunks,
                paper_master_excluded=self.paper_master_excluded,
                schedule_override=self.schedule_override,
                comm_schedule=self.comm_schedule,
                chunk_weights=self.chunk_weights,
            )(out)
        return out

    def report(self) -> str:
        from repro.core import report as report_mod

        if self.plan is None:
            raise ValueError(
                "call the region (or pass env_like to region_to_mpi) to "
                "build the residency plan before asking for a report")
        return report_mod.render_region(self.plan)


def region_to_mpi(
    region: pragma.ParallelRegion,
    mesh: Mesh,
    *,
    axis: str | tuple | None = None,
    lowering: str = "collective",
    fuse: bool = True,
    shard_inputs: bool = False,
    unroll_chunks: bool = False,
    env_like: Mapping[str, Any] | None = None,
    paper_master_excluded: bool | None = None,
    comm: str = "auto",
):
    """Deprecated: use ``omp.compile(region, mesh, omp.Options(...))``.

    Thin shim: translates the legacy kwargs to
    :class:`~repro.core.api.Options` — ``fuse=True`` +
    ``lowering="collective"`` becomes ``Lowering.FUSED``,
    ``fuse=False`` becomes ``Lowering.COLLECTIVE`` — and returns the
    :class:`~repro.core.api.Compiled` artifact (callable like the
    ``DistributedRegion`` it used to return, with ``.plan`` /
    ``.report()`` intact).
    """
    import warnings

    from repro.core import api

    warnings.warn(
        "omp.region_to_mpi() is deprecated; use omp.compile(region, mesh, "
        "omp.Options(lowering=..., comm=...)) instead",
        DeprecationWarning, stacklevel=2)
    if isinstance(region, pragma.ParallelFor):
        region = pragma.ParallelRegion((region,))
    if lowering == "master_worker":
        low = api.Lowering.MASTER_WORKER
    elif lowering != "collective":
        raise api.CompileError(f"unknown lowering {lowering!r}")
    elif fuse:
        low = api.Lowering.FUSED
    else:
        low = api.Lowering.COLLECTIVE
    options = api.Options(
        axis=axis,
        lowering=low,
        comm=comm,
        shard=(api.ShardPolicy.SLICE if shard_inputs
               else api.ShardPolicy.REPLICATE),
        unroll_chunks=unroll_chunks,
        paper_master_excluded=paper_master_excluded,
    )
    return api.compile(region, mesh, options, env_like=env_like)


# ---------------------------------------------------------------------------
# Fused execution (one shard_map for the whole region)
# ---------------------------------------------------------------------------


def _exchange_scope(keys) -> str:
    """Device scope of one boundary exchange (a packed group names all
    of its buffers)."""
    return "omp.exchange." + "+".join(keys)


def _execute_region(dr: DistributedRegion, env: dict) -> dict:
    from repro.core import comm_schedule as cs_mod

    if dr.plan.rank == 2:
        return _execute_region2(dr, env)
    tf._maybe_fault("region")
    rp = dr.plan
    mesh, axis = dr.mesh, rp.axis
    env_dtypes = {k: v.dtype for k, v in env.items()}
    sched = rp.comm_sched
    aggregate = sched is not None and sched.mode == "aggregate"
    if dr.use_pallas:
        from repro.core import pallas_lower as plx

        pallas_interp = plx.resolve_interpret(dr.pallas_interpret, mesh)
        span_of = {s[0]: s for s in plx.compute_region_spans(rp)}

    # exit layout is static — build specs up front
    slab_out = {k: lay for k, lay in rp.final_layout.items()
                if isinstance(lay, SlabLayout)}
    repl_out = [k for k, lay in rp.final_layout.items() if lay == REPLICATED]
    prior_out = [k for k, lay in slab_out.items() if lay.has_prior]

    def device_fn(env_all):
        d = jax.lax.axis_index(axis)
        st: dict[str, tuple] = {k: ("repl", v) for k, v in env_all.items()}
        span_results: dict[int, tuple] = {}

        def run_span(si, env_in, slab_stacks):
            """Fuse the span starting at stage ``si`` into one pallas
            kernel; later stages' external feeds come from the current
            ``st`` (spans never cross an exchange, so those entries are
            stable until each stage's merge runs)."""
            specs = []
            written: set = set()
            for sj in span_of[si]:
                sse = rp.stages[sj]
                sp_plan = sse.plan
                if sj == si:
                    ext, repl, fwd = dict(slab_stacks), dict(env_in), set()
                else:
                    ext, repl, fwd = {}, {}, set()
                    for key in sp_plan.context.env_keys:
                        dec = sp_plan.vars[key]
                        if dec.in_strategy in ("shard", "shard_halo"):
                            if sse.feeds[key] == "resident":
                                if key in written:
                                    fwd.add(key)    # in-VMEM hand-off
                                else:
                                    ext[key] = st[key][1]
                            else:               # "slice"
                                halo = (dec.halo if dec.halo is not None
                                        else (0, 0))
                                with jax.named_scope("omp.entry"):
                                    ext[key] = nest_mod.local_slabs(
                                        st[key][1], sp_plan.chunks, halo, d)
                        elif dec.in_strategy == "replicate":
                            repl[key] = st[key][1]
                specs.append(plx.SpanStage(
                    name=sse.name, plan=sp_plan, program=sse.stage,
                    ext_windows=ext, env_repl=repl,
                    forwarded=frozenset(fwd)))
                written |= plx._written_keys(sp_plan)
            for sj, res in zip(span_of[si],
                               plx.execute_span(specs, (d,),
                                                pallas_interp,
                                                mesh.devices.flat[0])):
                span_results[sj] = res
        # hoisted exchanges: (consumer stage idx, key) -> read window,
        # issued right after the producing stage (the prefetch)
        prefetched: dict[tuple[int, str], Any] = {}

        def issue_prefetch(after_idx):
            for grp in sched.groups_after(after_idx):
                items = []
                for ev in grp.events:
                    _, stacks, sbase, scover, sprior, sdtype = st[ev.key]
                    items.append(cs_mod.HaloItem(
                        stacks=stacks, chunks=ev.chunks, shifts=ev.shifts,
                        prior=sprior, bases=(sbase,), covers=(scover,),
                        dtype=sdtype))
                with jax.named_scope(_exchange_scope(grp.keys)):
                    wins = cs_mod.aggregated_halo_exchange(
                        items, axis=axis,
                        num_devices=grp.events[0].num_devices[0],
                        device_index=d)
                for ev, win in zip(grp.events, wins):
                    prefetched[(ev.consumer_idx, ev.key)] = win

        def materialize(key):
            tag = st[key][0]
            if tag == "repl":
                return st[key][1]
            _, stacks, base, cover, prior, dtype = st[key]
            with jax.named_scope(f"omp.gather.{key}"):
                g = jax.lax.all_gather(stacks, axis, axis=1, tiled=False)
                flat = g.reshape((-1,) + g.shape[3:])[:cover].astype(dtype)
                if prior is None:
                    full = flat
                else:
                    full = jax.lax.dynamic_update_slice_in_dim(
                        prior, flat, base, 0)
            st[key] = ("repl", full)
            return full

        for si, se in enumerate(rp.stages):
            for k in se.gathers:
                materialize(k)

            if se.kind == "serial":
                env_full = {k: e[1] for k, e in st.items() if e[0] == "repl"}
                with jax.named_scope(f"omp.stage.{se.name}"):
                    upd = se.stage.fn(env_full)
                for k, v in upd.items():
                    st[k] = ("repl", jnp.asarray(v))
                continue

            plan = se.plan
            t = plan.loop.trip_count
            if t == 0:
                for key, dec in plan.vars.items():
                    if dec.out_strategy == "reduce":
                        rop = red_mod.get_reduction(dec.reduction_op)
                        info = plan.context.vars[key]
                        val = red_mod.identity_like(
                            rop, jnp.zeros(info.write.value_shape,
                                           info.write.value_dtype))
                        if key in st:
                            val = rop.pairwise(materialize(key), val)
                        st[key] = ("repl", val)
                continue

            env_in: dict[str, Any] = {}
            slab_stacks: dict[str, Any] = {}
            for key in plan.context.env_keys:
                dec = plan.vars[key]
                if dec.in_strategy in ("shard", "shard_halo"):
                    feed = se.feeds[key]
                    if feed == "resident":
                        slab_stacks[key] = st[key][1]
                    elif feed == "halo":
                        if aggregate:
                            # the scheduler issued this exchange right
                            # after its producer (prefetched window)
                            slab_stacks[key] = prefetched.pop((si, key))
                        else:
                            # neighbor ppermute ring shifts: the planned
                            # point-to-point boundary exchange (§3.1.4)
                            _, stacks, sbase, scover, sprior, sdtype = st[key]
                            h = dec.halo if dec.halo is not None else (0, 0)
                            with jax.named_scope(_exchange_scope((key,))):
                                slab_stacks[key] = comm_mod.halo_exchange(
                                    stacks, axis=axis,
                                    num_devices=plan.chunks.num_devices,
                                    device_index=d, chunk=plan.chunks.chunk,
                                    delta_min=h[0] - sbase,
                                    delta_max=h[1] - sbase,
                                    prior=sprior, base=sbase, cover=scover,
                                    dtype=sdtype)
                    else:
                        halo = dec.halo if dec.halo is not None else (0, 0)
                        with jax.named_scope("omp.entry"):
                            slab_stacks[key] = nest_mod.local_slabs(
                                st[key][1], plan.chunks, halo, d)
                elif dec.in_strategy == "replicate":
                    env_in[key] = st[key][1]

            with jax.named_scope(f"omp.stage.{se.name}"), \
                    tf.indirect_scope(plan):
                if not dr.use_pallas:
                    carry, ys = tf._run_local_chunks(
                        plan, se.stage, env_in, slab_stacks, (d,),
                        dr.unroll_chunks)
                else:
                    if si not in span_results:
                        run_span(si, env_in, slab_stacks)
                    carry, ys = span_results.pop(si)

            # Cross-device combines of this stage's merges: issued
            # per-key inline, or deferred into fused flat collectives
            # (one launch per (collective, dtype) group) when scheduled.
            pending: dict[tuple[str, str], tuple[str, Any]] = {}
            for key, dec in plan.vars.items():
                info = plan.context.vars[key]
                if dec.out_strategy == "identity":
                    st[key] = ("slab", ys[key], 0, t, None, info.dtype)
                elif dec.out_strategy == "partial":
                    b = dec.write_map.b
                    prev = st.get(key)
                    if (prev is not None and prev[0] == "slab"
                            and prev[2] == b and prev[3] == t):
                        prior = prev[4]     # same interval: chain the prior
                    else:
                        prior = st[key][1]  # replicated (planner enforced)
                    st[key] = ("slab", ys[key], b, t, prior, info.dtype)
                elif dec.out_strategy == "scatter":
                    buf, mask = carry[key]
                    if aggregate:
                        pending[(key, "buf")] = ("psum", buf)
                        pending[(key, "mask")] = \
                            ("psum", mask.astype(jnp.int32))
                        continue
                    with jax.named_scope(f"omp.combine.{key}"):
                        summed = jax.lax.psum(buf, axis)
                        m = jax.lax.psum(mask.astype(jnp.int32), axis)
                        prior = st[key][1]
                        vmask = (m > 0).reshape(
                            (-1,) + (1,) * (summed.ndim - 1))
                        st[key] = ("repl", jnp.where(
                            vmask, summed.astype(prior.dtype), prior))
                elif dec.out_strategy == "put":
                    j_star = (t - 1) // plan.chunks.chunk
                    owner = j_star % plan.chunks.num_devices
                    with jax.named_scope(f"omp.combine.{key}"):
                        val = jnp.where(d == owner, carry[key],
                                        jnp.zeros_like(carry[key]))
                        if aggregate:
                            pending[(key, "put")] = ("psum", val)
                            continue
                        st[key] = ("repl", jax.lax.psum(val, axis))
                elif dec.out_strategy == "reduce":
                    rop = red_mod.get_reduction(dec.reduction_op)
                    if aggregate and rop.collective in ("psum", "pmax",
                                                        "pmin"):
                        pending[(key, "red")] = (rop.collective, carry[key])
                        continue
                    with jax.named_scope(f"omp.combine.{key}"):
                        val = red_mod.cross_device_combine(
                            rop, carry[key], axis)
                        if key in st:
                            val = rop.pairwise(st[key][1], val)
                    st[key] = ("repl", val)

            if pending:
                with jax.named_scope("omp.combine"):
                    combined = cs_mod.fused_collectives(pending, axis)
                    for key, dec in plan.vars.items():
                        if dec.out_strategy == "scatter":
                            summed = combined[(key, "buf")]
                            m = combined[(key, "mask")]
                            prior = st[key][1]
                            vmask = (m > 0).reshape(
                                (-1,) + (1,) * (summed.ndim - 1))
                            st[key] = ("repl", jnp.where(
                                vmask, summed.astype(prior.dtype), prior))
                        elif dec.out_strategy == "put":
                            st[key] = ("repl", combined[(key, "put")])
                        elif dec.out_strategy == "reduce" \
                                and (key, "red") in combined:
                            rop = red_mod.get_reduction(dec.reduction_op)
                            val = combined[(key, "red")]
                            if key in st:
                                val = rop.pairwise(st[key][1], val)
                            st[key] = ("repl", val)

            if aggregate:
                issue_prefetch(si)

        with jax.named_scope("omp.exit"):
            outs_repl = {k: st[k][1] for k in repl_out}
            outs_slab = {k: st[k][1][:, None] for k in slab_out}
            outs_prior = {k: st[k][4] for k in prior_out}
        return outs_repl, outs_slab, outs_prior

    in_specs = ({k: P() for k in env},)
    out_specs = (
        {k: P() for k in repl_out},
        {k: slab_spec(axis) for k in slab_out},
        {k: P() for k in prior_out},
    )
    if not rp.touched_keys:
        return dict(env)

    outs_repl, outs_slab, outs_prior = shard_map(
        device_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
    )(env)

    # --- reassembly at the jit level (layout, not messages) ---------------
    result = dict(env)
    for key in repl_out:
        result[key] = outs_repl[key]
    with jax.named_scope("omp.exit"):
        for key, lay in slab_out.items():
            g = outs_slab[key]                   # (n_loc, P, c, *rest)
            flat = g.reshape((-1,) + g.shape[3:])[:lay.cover]
            flat = flat.astype(env_dtypes.get(key, flat.dtype))
            if lay.has_prior:
                result[key] = jax.lax.dynamic_update_slice_in_dim(
                    outs_prior[key], flat, lay.base, 0)
            else:
                result[key] = flat
    return result


def _execute_region2(dr: DistributedRegion, env: dict) -> dict:
    """Fused execution of a rank-2 region: ONE shard_map over the 2-D
    mesh; slabs stay resident as ``(n_i, c_i, n_j, c_j, *rest)`` stacks,
    halo boundaries run as row+column ``ppermute`` rings."""
    from repro.core import comm_schedule as cs_mod

    tf._maybe_fault("region2")

    rp = dr.plan
    mesh = dr.mesh
    ax_i, ax_j = rp.axis
    env_dtypes = {k: v.dtype for k, v in env.items()}
    sched = rp.comm_sched
    aggregate = sched is not None and sched.mode == "aggregate"
    if dr.use_pallas:
        from repro.core import pallas_lower as plx

        pallas_interp = plx.resolve_interpret(dr.pallas_interpret, mesh)
        span_of = {s[0]: s for s in plx.compute_region_spans(rp)}

    slab_out = {k: lay for k, lay in rp.final_layout.items()
                if isinstance(lay, SlabLayout2)}
    repl_out = [k for k, lay in rp.final_layout.items() if lay == REPLICATED]
    prior_out = [k for k, lay in slab_out.items() if lay.has_prior]

    def device_fn(env_all):
        d_i = jax.lax.axis_index(ax_i)
        d_j = jax.lax.axis_index(ax_j)
        st: dict[str, tuple] = {k: ("repl", v) for k, v in env_all.items()}
        prefetched: dict[tuple[int, str], Any] = {}
        span_results: dict[int, tuple] = {}

        def run_span(si, env_in, slab_stacks):
            specs = []
            written: set = set()
            for sj in span_of[si]:
                sse = rp.stages[sj]
                sp_plan = sse.plan
                sch_i, sch_j = sp_plan.chunks_axes
                if sj == si:
                    ext, repl, fwd = dict(slab_stacks), dict(env_in), set()
                else:
                    ext, repl, fwd = {}, {}, set()
                    for key in sp_plan.context.env_keys:
                        dec = sp_plan.vars[key]
                        if dec.in_strategy in ("shard", "shard_halo"):
                            if sse.feeds[key] == "resident":
                                if key in written:
                                    fwd.add(key)    # in-VMEM hand-off
                                else:
                                    ext[key] = st[key][1]
                            else:               # "slice"
                                halos = (dec.halo_axes
                                         if dec.halo_axes is not None
                                         else ((0, 0), (0, 0)))
                                x = st[key][1]
                                with jax.named_scope("omp.entry"):
                                    if dec.shard_ndim == 2:
                                        ext[key] = nest_mod.local_slabs2(
                                            x, (sch_i, sch_j), halos,
                                            (d_i, d_j))
                                    else:
                                        ext[key] = nest_mod.local_slabs(
                                            x, sch_i, halos[0], d_i)
                        elif dec.in_strategy == "replicate":
                            repl[key] = st[key][1]
                specs.append(plx.SpanStage(
                    name=sse.name, plan=sp_plan, program=sse.stage,
                    ext_windows=ext, env_repl=repl,
                    forwarded=frozenset(fwd)))
                written |= plx._written_keys(sp_plan)
            for sj, res in zip(span_of[si],
                               plx.execute_span(specs, (d_i, d_j),
                                                pallas_interp,
                                                mesh.devices.flat[0])):
                span_results[sj] = res

        def issue_prefetch(after_idx):
            for grp in sched.groups_after(after_idx):
                items = []
                for ev in grp.events:
                    _, stacks, bases, covers, sprior, sdtype = st[ev.key]
                    items.append(cs_mod.HaloItem(
                        stacks=stacks, chunks=ev.chunks, shifts=ev.shifts,
                        prior=sprior, bases=bases, covers=covers,
                        dtype=sdtype))
                with jax.named_scope(_exchange_scope(grp.keys)):
                    wins = cs_mod.aggregated_halo_exchange2(
                        items, axes=(ax_i, ax_j),
                        num_devices=grp.events[0].num_devices,
                        device_indices=(d_i, d_j))
                for ev, win in zip(grp.events, wins):
                    prefetched[(ev.consumer_idx, ev.key)] = win

        def materialize(key):
            tag = st[key][0]
            if tag == "repl":
                return st[key][1]
            _, stacks, bases, covers, prior, dtype = st[key]
            with jax.named_scope(f"omp.gather.{key}"):
                g = jax.lax.all_gather(stacks, ax_i, axis=1, tiled=False)
                g = jax.lax.all_gather(g, ax_j, axis=4, tiled=False)
                flat = g.reshape(
                    (g.shape[0] * g.shape[1] * g.shape[2],
                     g.shape[3] * g.shape[4] * g.shape[5]) + g.shape[6:])
                flat = flat[:covers[0], :covers[1]].astype(dtype)
                if prior is None:
                    full = flat
                else:
                    full = jax.lax.dynamic_update_slice(
                        prior, flat, bases + (0,) * (flat.ndim - 2))
            st[key] = ("repl", full)
            return full

        for si, se in enumerate(rp.stages):
            for k in se.gathers:
                materialize(k)

            if se.kind == "serial":
                env_full = {k: e[1] for k, e in st.items() if e[0] == "repl"}
                with jax.named_scope(f"omp.stage.{se.name}"):
                    upd = se.stage.fn(env_full)
                for k, v in upd.items():
                    st[k] = ("repl", jnp.asarray(v))
                continue

            plan = se.plan
            ch_i, ch_j = plan.chunks_axes
            trips = plan.nest.trip_counts
            if plan.nest.total_trip == 0:
                for key, dec in plan.vars.items():
                    if dec.out_strategy == "reduce":
                        rop = red_mod.get_reduction(dec.reduction_op)
                        info = plan.context.vars[key]
                        val = red_mod.identity_like(
                            rop, jnp.zeros(info.write.value_shape,
                                           info.write.value_dtype))
                        if key in st:
                            val = rop.pairwise(materialize(key), val)
                        st[key] = ("repl", val)
                continue

            env_in: dict[str, Any] = {}
            slab_stacks: dict[str, Any] = {}
            for key in plan.context.env_keys:
                dec = plan.vars[key]
                if dec.in_strategy == "shard_halo":
                    feed = se.feeds[key]
                    if feed == "resident":
                        slab_stacks[key] = st[key][1]
                    elif feed == "halo":
                        if aggregate:
                            slab_stacks[key] = prefetched.pop((si, key))
                            continue
                        _, stacks, bases, covers, prior, dtype = st[key]
                        halos = dec.halo_axes
                        with jax.named_scope(_exchange_scope((key,))):
                            slab_stacks[key] = comm_mod.halo_exchange2(
                                stacks, axes=(ax_i, ax_j),
                                num_devices=(ch_i.num_devices,
                                             ch_j.num_devices),
                                device_indices=(d_i, d_j),
                                chunks=(ch_i.chunk, ch_j.chunk),
                                deltas=tuple(
                                    (h[0] - b, h[1] - b)
                                    for h, b in zip(halos, bases)),
                                prior=prior, bases=bases, covers=covers,
                                dtype=dtype)
                    else:
                        halos = (dec.halo_axes if dec.halo_axes is not None
                                 else ((0, 0), (0, 0)))
                        x = st[key][1]
                        with jax.named_scope("omp.entry"):
                            if dec.shard_ndim == 2:
                                slab_stacks[key] = nest_mod.local_slabs2(
                                    x, (ch_i, ch_j), halos, (d_i, d_j))
                            else:
                                slab_stacks[key] = nest_mod.local_slabs(
                                    x, ch_i, halos[0], d_i)
                elif dec.in_strategy == "replicate":
                    env_in[key] = st[key][1]

            with jax.named_scope(f"omp.stage.{se.name}"):
                if not dr.use_pallas:
                    carry, ys = tf._run_local_chunks(
                        plan, se.stage, env_in, slab_stacks, (d_i, d_j),
                        dr.unroll_chunks)
                else:
                    if si not in span_results:
                        run_span(si, env_in, slab_stacks)
                    carry, ys = span_results.pop(si)

            reduce_items: dict[str, tuple] = {}
            for key, dec in plan.vars.items():
                info = plan.context.vars[key]
                if dec.out_strategy == "identity":
                    st[key] = ("slab2", ys[key], (0, 0), trips, None,
                               info.dtype)
                elif dec.out_strategy == "partial":
                    bases = tuple(m.b for m in dec.write_maps)
                    prev = st.get(key)
                    if (prev is not None and prev[0] == "slab2"
                            and prev[2] == bases and prev[3] == trips):
                        prior = prev[4]     # same rectangle: chain the prior
                    else:
                        prior = st[key][1]  # replicated (planner enforced)
                    st[key] = ("slab2", ys[key], bases, trips, prior,
                               info.dtype)
                elif dec.out_strategy == "reduce":
                    rop = red_mod.get_reduction(dec.reduction_op)
                    if aggregate:
                        reduce_items[key] = (rop, carry[key])
                        continue
                    with jax.named_scope(f"omp.combine.{key}"):
                        val = red_mod.cross_device_combine(
                            rop, carry[key], (ax_i, ax_j))
                        if key in st:
                            val = rop.pairwise(st[key][1], val)
                    st[key] = ("repl", val)

            if reduce_items:
                with jax.named_scope("omp.combine"):
                    combined = cs_mod.fused_cross_device_combine(
                        reduce_items, (ax_i, ax_j))
                    for key, val in combined.items():
                        rop = reduce_items[key][0]
                        if key in st:
                            val = rop.pairwise(st[key][1], val)
                        st[key] = ("repl", val)

            if aggregate:
                issue_prefetch(si)

        with jax.named_scope("omp.exit"):
            outs_repl = {k: st[k][1] for k in repl_out}
            outs_slab = {k: st[k][1][:, None, :, :, None]
                         for k in slab_out}
            outs_prior = {k: st[k][4] for k in prior_out}
        return outs_repl, outs_slab, outs_prior

    in_specs = ({k: P() for k in env},)
    out_specs = (
        {k: P() for k in repl_out},
        {k: slab_spec((ax_i, ax_j)) for k in slab_out},
        {k: P() for k in prior_out},
    )
    if not rp.touched_keys:
        return dict(env)

    outs_repl, outs_slab, outs_prior = shard_map(
        device_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
    )(env)

    # --- reassembly at the jit level (layout, not messages) ---------------
    result = dict(env)
    for key in repl_out:
        result[key] = outs_repl[key]
    with jax.named_scope("omp.exit"):
        for key, lay in slab_out.items():
            g = outs_slab[key]           # (n_i, P_i, c_i, n_j, P_j, c_j, *)
            flat = g.reshape(
                (g.shape[0] * g.shape[1] * g.shape[2],
                 g.shape[3] * g.shape[4] * g.shape[5]) + g.shape[6:])
            flat = flat[:lay.covers[0], :lay.covers[1]]
            flat = flat.astype(env_dtypes.get(key, flat.dtype))
            if lay.has_prior:
                result[key] = jax.lax.dynamic_update_slice(
                    outs_prior[key], flat,
                    lay.bases + (0,) * (flat.ndim - 2))
            else:
                result[key] = flat
    return result
