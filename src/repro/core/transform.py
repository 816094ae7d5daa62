"""OMP→"MPI" code generation (paper §3.1.3–3.1.4).

Two executors for a :class:`~repro.core.pragma.ParallelFor` program:

* :func:`run_reference` — the *shared-memory* ("OpenMP") semantics on the
  local device.  This is the oracle: the paper's "correct by construction"
  claim is validated as ``omp.compile(pf, mesh)(env) == pf(env)``.

* :class:`DistributedProgram` (built by :func:`repro.core.api.compile`'s
  **lower** pass) — executes the block over a mesh axis under
  ``jax.shard_map`` using the :class:`~repro.core.plan.DistPlan`
  strategies.  Two lowerings:

  - ``"collective"`` — TPU-native: chunk-cyclic layout + balanced
    collectives (psum / sharded slabs).  This is the production path.
  - ``"master_worker"`` — paper-faithful: rank 0 owns the shared memory;
    every IN buffer is *sent* from rank 0 to each worker and every OUT
    slab is sent back and re-broadcast, as explicit
    ``collective-permute`` pairs.  It reproduces the communication shape
    of the paper's Fig. 1b (all traffic through the master's links) and
    exists as the measurable baseline for EXPERIMENTS.md §Perf-A.

Both executors transform ONE block.  Whole programs (chains of blocks
with inter-loop residency planning) compile to a
:class:`repro.core.region.DistributedRegion`, which reuses this module's
chunk-execution machinery (`_run_local_chunks`) inside a single fused
shard_map; per-loop staging via this module is its measurable baseline
(EXPERIMENTS.md §Perf-C).

The public surface is :func:`repro.core.api.compile`; :func:`to_mpi`
remains as a deprecation shim over it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Mapping

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.compat import shard_map
from repro.core import nest as nest_mod
from repro.core import pragma, reduction as red_mod, tile_eval, timing
from repro.core.context import ReadKind, VarClass, WriteKind
from repro.core.loop import LoopNotCanonical, analyze_loop
from repro.core.nest import LoopNest, ShiftedWindow, SubstitutionFailed  # noqa: F401 (re-export)
from repro.core.plan import DistPlan, make_plan


# ---------------------------------------------------------------------------
# Shared-memory reference executor ("the OpenMP block")
# ---------------------------------------------------------------------------


def run_reference(program: pragma.ParallelFor, env: Mapping[str, Any]) -> dict:
    """Execute with OpenMP shared-memory semantics on the local device.

    Reads observe the pre-loop environment (iterations are concurrent in
    OpenMP; racy read-after-write across iterations is UB there and
    unsupported here — see DESIGN.md).
    """
    if program.rank == 2:
        return _run_reference2(program, env)
    loop = analyze_loop(program.start, program.stop, program.step)
    env = {k: jnp.asarray(v) for k, v in env.items()}
    out = dict(env)
    t = loop.trip_count
    if t == 0:
        # A zero-trip loop writes nothing — except that a reduction
        # clause *defines* its variable as the op identity even over an
        # empty iteration space (OpenMP initialises the private copy
        # before any iteration runs).  Buffers already in env keep their
        # value (identity folds are no-ops); fresh reduction outputs
        # must still exist, matching the distributed executors.
        fresh = [k for k in program.reduction if k not in out]
        if fresh:
            upds = jax.eval_shape(
                program.body, jax.ShapeDtypeStruct((), jnp.int32), env)
            for key in fresh:
                rop = red_mod.get_reduction(program.reduction[key])
                out[key] = red_mod.identity_like(
                    rop, jnp.zeros(upds[key].value.shape,
                                   upds[key].value.dtype))
        return out

    ivec = program.start + program.step * jnp.arange(t, dtype=jnp.int32)
    updates = jax.vmap(lambda i: program.body(i, env))(ivec)
    for key, upd in updates.items():
        if isinstance(upd, pragma.At):
            out[key] = out[key].at[upd.idx].set(upd.value)
        elif isinstance(upd, pragma.Put):
            out[key] = upd.value[t - 1]
        elif isinstance(upd, pragma.Red):
            rop = red_mod.get_reduction(program.reduction[key])
            folded = rop.local_fold(upd.value, 0)
            if key in env:
                folded = rop.pairwise(env[key], folded)
            out[key] = folded
        else:
            raise LoopNotCanonical(
                f"update for {key!r} must be omp.at/omp.put/omp.red"
            )
    return out


def _run_reference2(program: pragma.ParallelFor, env: Mapping[str, Any]) -> dict:
    """Shared-memory reference for a ``collapse=2`` nest: the body is
    vmapped over the full cross product of both iteration spaces."""
    nest = LoopNest.from_program(program)
    env = {k: jnp.asarray(v) for k, v in env.items()}
    out = dict(env)
    t_i, t_j = nest.trip_counts
    if t_i == 0 or t_j == 0:
        fresh = [k for k in program.reduction if k not in out]
        if fresh:
            zero = jax.ShapeDtypeStruct((), jnp.int32)
            upds = jax.eval_shape(program.body, zero, zero, env)
            for key in fresh:
                rop = red_mod.get_reduction(program.reduction[key])
                out[key] = red_mod.identity_like(
                    rop, jnp.zeros(upds[key].value.shape,
                                   upds[key].value.dtype))
        return out

    ax_i, ax_j = nest.axes
    ivec = ax_i.start + ax_i.step * jnp.arange(t_i, dtype=jnp.int32)
    jvec = ax_j.start + ax_j.step * jnp.arange(t_j, dtype=jnp.int32)
    updates = jax.vmap(
        lambda i: jax.vmap(lambda j: program.body(i, j, env))(jvec))(ivec)
    for key, upd in updates.items():
        if isinstance(upd, pragma.At):
            out[key] = out[key].at[upd.idx].set(upd.value)
        elif isinstance(upd, pragma.Red):
            rop = red_mod.get_reduction(program.reduction[key])
            folded = rop.local_fold(upd.value, (0, 1))
            if key in env:
                folded = rop.pairwise(env[key], folded)
            out[key] = folded
        else:
            raise LoopNotCanonical(
                f"update for {key!r} must be omp.at/omp.red in a "
                "collapse=2 nest"
            )
    return out


# ---------------------------------------------------------------------------
# Distributed program
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DistributedProgram:
    """The generated "MPI" program for one parallel block."""

    program: pragma.ParallelFor
    mesh: Mesh
    plan: DistPlan | None
    axis: str = "data"
    lowering: str = "collective"
    shard_inputs: bool = False
    unroll_chunks: bool = False
    paper_master_excluded: bool | None = None
    schedule_override: pragma.Schedule | None = None
    comm_schedule: str = "aggregate"    # fuse per-block combines when set
    use_pallas: bool = False            # Lowering.PALLAS: tiled kernels
    pallas_interpret: bool | None = None
    chunk_weights: tuple | None = None  # straggler-weighted chunk deal

    def __call__(self, env: Mapping[str, Any]) -> dict:
        env = {k: jnp.asarray(v) for k, v in env.items()}
        with jax.named_scope(f"omp.block.{self.program.name}"):
            return _execute(self, env)

    def report(self) -> str:
        from repro.core import report as report_mod

        if self.plan is None:
            raise ValueError("call the program (or pass env_like) to build "
                             "the plan before asking for a report")
        return report_mod.render_plan(self.plan)


def resolve_axes(program_or_rank, mesh: Mesh, axis):
    """Resolve the mesh-axis clause against the program's nest rank.

    Returns ``(axis, num_devices)`` — scalars for rank-1, matching
    2-tuples for rank-2 (defaulting to ``("i", "j")`` when present in
    the mesh, else the first two mesh axes).
    """
    rank = (program_or_rank if isinstance(program_or_rank, int)
            else program_or_rank.rank)
    names = tuple(mesh.axis_names)
    if rank == 2:
        if axis is None:
            if "i" in names and "j" in names:
                axis = ("i", "j")
            elif len(names) >= 2:
                axis = names[:2]
            else:
                raise ValueError(
                    f"collapse=2 needs a 2-D mesh; got axes {names}")
        if not isinstance(axis, tuple) or len(axis) != 2 \
                or axis[0] == axis[1]:
            raise ValueError(
                f"collapse=2 needs two distinct mesh axes, got {axis!r}")
        for a in axis:
            if a not in names:
                raise ValueError(f"axis {a!r} not in mesh axes {names}")
        return axis, tuple(int(mesh.shape[a]) for a in axis)
    if axis is None:
        axis = "data"
    if axis not in names:
        raise ValueError(f"axis {axis!r} not in mesh axes {names}")
    return axis, mesh.shape[axis]


def mesh_axis_sizes(mesh: Mesh, axis):
    """Device count(s) along an already-resolved axis clause: a scalar
    for one named axis, a matching tuple for a rank-2 axis pair."""
    if isinstance(axis, tuple):
        return tuple(int(mesh.shape[a]) for a in axis)
    return mesh.shape[axis]


def to_mpi(
    program: pragma.ParallelFor,
    mesh: Mesh,
    *,
    axis: str | tuple | None = None,
    lowering: str = "collective",
    shard_inputs: bool = False,
    keep_sharded: bool = False,
    unroll_chunks: bool = False,
    env_like: Mapping[str, Any] | None = None,
    paper_master_excluded: bool | None = None,
):
    """Deprecated: use ``omp.compile(program, mesh, omp.Options(...))``.

    Thin shim: translates the legacy kwargs to
    :class:`~repro.core.api.Options` and returns the
    :class:`~repro.core.api.Compiled` artifact (callable like the
    ``DistributedProgram`` it used to return, with ``.plan`` /
    ``.report()`` intact).
    """
    import warnings

    from repro.core import api

    warnings.warn(
        "omp.to_mpi() is deprecated; use omp.compile(program, mesh, "
        "omp.Options(lowering=..., shard=...)) instead",
        DeprecationWarning, stacklevel=2)
    options = api.Options(
        axis=axis,
        lowering=lowering,
        shard=(api.ShardPolicy.SLICE if shard_inputs
               else api.ShardPolicy.REPLICATE),
        keep_sharded=keep_sharded,
        unroll_chunks=unroll_chunks,
        paper_master_excluded=paper_master_excluded,
    )
    return api.compile(program, mesh, options, env_like=env_like)


# ---------------------------------------------------------------------------
# Execution
# ---------------------------------------------------------------------------

#: Fault-injection hook (repro.runtime.fault_injection installs a
#: callable here inside ``inject()``); called with a site name at the
#: python entry of each distributed executor.  ``None`` in production.
_fault_hook = None


def _maybe_fault(site: str) -> None:
    if _fault_hook is not None:
        _fault_hook(site)


def _execute(dp: DistributedProgram, env: dict) -> dict:
    program = dp.program
    if dp.plan is None:
        dp.plan = make_plan(
            program, env, mesh_axis_sizes(dp.mesh, dp.axis), axis=dp.axis,
            lowering=dp.lowering, shard_inputs=dp.shard_inputs,
            paper_master_excluded=dp.paper_master_excluded,
            schedule=dp.schedule_override,
            weights=dp.chunk_weights,
        )
    plan = dp.plan
    t = plan.nest.total_trip
    out = dict(env)
    if t == 0:
        for key, dec in plan.vars.items():
            if dec.out_strategy == "reduce":
                rop = red_mod.get_reduction(dec.reduction_op)
                info = plan.context.vars[key]
                zero = red_mod.identity_like(
                    rop, jnp.zeros(info.write.value_shape, info.write.value_dtype))
                out[key] = rop.pairwise(env[key], zero) if key in env else zero
        return out

    if plan.rank == 2:
        return _execute_collective2(dp, env)
    if plan.lowering == "collective":
        return _execute_collective(dp, env)
    return _execute_master_worker(dp, env)


def _chunk_iteration_vectors(plan, j, dtype=jnp.int32):
    """Iteration numbers, validity mask and clamped loop indices of chunk j."""
    c = plan.chunks.chunk
    t = plan.loop.trip_count
    ks = j * c + jnp.arange(c, dtype=dtype)
    valid = ks < t
    kc = jnp.minimum(ks, t - 1)
    ivec = plan.loop.start + plan.loop.step * kc
    return ks, valid, kc, ivec


def _make_env_sub(plan, env_in, slabs_q, k0):
    """Environment seen by the body inside one chunk."""
    env_sub: dict[str, Any] = {}
    for key in plan.context.env_keys:
        dec = plan.vars[key]
        info = plan.context.vars[key]
        if dec.in_strategy == "shard":
            env_sub[key] = ShiftedWindow(
                slabs_q[key], (k0,), info.shape, info.dtype)
        elif dec.in_strategy == "shard_halo":
            # slab row t holds position k0 + b_min + t
            env_sub[key] = ShiftedWindow(
                slabs_q[key], (k0 + dec.halo[0],), info.shape, info.dtype)
        elif dec.in_strategy == "replicate":
            env_sub[key] = env_in[key]
        else:  # unused inside the body: placeholder, DCE'd by XLA
            env_sub[key] = jnp.zeros(info.shape, info.dtype)
    return env_sub


def _apply_chunk_updates(plan, updates, carry, ys, j, valid, shapes):
    """Fold one chunk's updates into the scan carry / per-chunk outputs."""
    t = plan.loop.trip_count
    for key, dec in plan.vars.items():
        if dec.out_strategy == "none":
            continue
        upd = updates[key]
        if dec.out_strategy in ("identity", "partial"):
            ys[key] = upd.value
        elif dec.out_strategy == "scatter":
            shape0 = shapes[key][0]
            # positions from true iteration numbers of this chunk
            ks = j * plan.chunks.chunk + jnp.arange(plan.chunks.chunk)
            pos = dec.write_map.a * ks + dec.write_map.b
            pos = jnp.where(valid, pos, shape0)  # OOB -> dropped
            buf, mask = carry[key]
            buf = buf.at[pos].set(upd.value, mode="drop")
            mask = mask.at[pos].set(True, mode="drop")
            carry[key] = (buf, mask)
        elif dec.out_strategy == "put":
            j_star = (t - 1) // plan.chunks.chunk
            lane = (t - 1) - j_star * plan.chunks.chunk
            row = jax.lax.dynamic_index_in_dim(upd.value, lane, 0, keepdims=False)
            carry[key] = jnp.where(j == j_star, row, carry[key])
        elif dec.out_strategy == "reduce":
            rop = red_mod.get_reduction(dec.reduction_op)
            ident = red_mod.identity_like(rop, upd.value)
            vmask = valid.reshape((-1,) + (1,) * (upd.value.ndim - 1))
            contrib = jnp.where(vmask, upd.value, ident)
            part = rop.local_fold(contrib, 0)
            carry[key] = rop.pairwise(carry[key], part)
    return carry, ys


def _init_carry(plan):
    carry: dict[str, Any] = {}
    for key, dec in plan.vars.items():
        info = plan.context.vars[key]
        if dec.out_strategy == "scatter":
            carry[key] = (
                jnp.zeros(info.shape, info.dtype),
                jnp.zeros((info.shape[0],), jnp.bool_),
            )
        elif dec.out_strategy == "put":
            carry[key] = jnp.zeros(info.shape, info.dtype)
        elif dec.out_strategy == "reduce":
            rop = red_mod.get_reduction(dec.reduction_op)
            carry[key] = red_mod.identity_like(
                rop, jnp.zeros(info.write.value_shape, info.write.value_dtype))
    return carry


def _slot_table(ch):
    """(n_loc, P) table of global chunk ids per (local chunk, device)
    slot, or ``None`` for the plain cyclic deal (where the chunk id is
    just ``q * P + d``)."""
    if ch.slot_map is None:
        return None
    return jnp.asarray(np.asarray(ch.slot_map, dtype=np.int32).reshape(
        ch.local_chunks, ch.num_devices))


def indirect_scope(plan):
    """The device scope ``omp.indirect.<keys>`` around the chunk compute
    of a stage that reads through an index array (nested in its
    ``omp.stage.<name>``), counted as one indirect stage; an empty
    context for any other stage."""
    keys = plan.indirect_reads
    if not keys:
        return contextlib.nullcontext()
    timing.count_indirect(plan.nest.total_trip * sum(
        plan.context.vars[k].read.index_elems for k in keys))
    return jax.named_scope("omp.indirect." + "+".join(sorted(keys)))


def _run_local_chunks(plan, program, env_in, slab_stacks, device_indices,
                      unroll_chunks=False):
    """Run this device's local chunks of a stage (rank 1 or 2); returns
    ``(carry, ys)`` with ys values laid out ``(n_0, c_0[, n_1, c_1],
    *rest)``.

    The body is evaluated once over the whole local chunk stack, every
    window read and every unit-stride read of a replicated buffer served
    as a slice (:func:`tile_eval.eval_local_chunks`); a body whose
    window reads the evaluator cannot serve runs as a scan of vmapped
    chunks instead.  Each path taken is counted (``chunk_eval_sliced``
    / ``chunk_eval_scan`` in ``omp.timing_stats``).
    """
    def sliced(device_indices, slab_stacks, env_in):
        values = tile_eval.eval_local_chunks(plan, program, env_in,
                                             slab_stacks, device_indices)
        return tile_eval.merge_chunk_values(plan, values, device_indices)

    try:
        # one jit: a bare (eager) call compiles the stage once, not once
        # per primitive of the evaluated body
        out = jax.jit(sliced)(tuple(device_indices), slab_stacks, env_in)
    except SubstitutionFailed:
        timing.count_chunk_eval("scan")
        if plan.rank == 2:
            return _scan_local_chunks2(plan, program, env_in, slab_stacks,
                                       device_indices, unroll_chunks)
        return _scan_local_chunks(plan, program, env_in, slab_stacks,
                                  device_indices[0], unroll_chunks)
    timing.count_chunk_eval("sliced")
    return out


def _scan_local_chunks(plan, program, env_in, slab_stacks, worker_index,
                       unroll_chunks=False):
    """Scan this device's chunks, the body vmapped over each chunk's
    lanes; returns (carry, ys_stacked)."""
    ch = plan.chunks
    shapes = {k: plan.context.vars[k].shape for k in plan.vars}
    carry0 = _init_carry(plan)
    slot_table = _slot_table(ch)

    def one_chunk(carry, q):
        if slot_table is None:
            j = q * ch.num_devices + worker_index
        else:
            j = slot_table[q, worker_index]
        k0 = j * ch.chunk
        ks, valid, kc, ivec = _chunk_iteration_vectors(plan, j)
        if isinstance(q, int):
            # static chunk index: plain slices instead of dynamic gathers
            slabs_q = {k: v[q] for k, v in slab_stacks.items()}
        else:
            slabs_q = {k: jax.lax.dynamic_index_in_dim(v, q, 0,
                                                       keepdims=False)
                       for k, v in slab_stacks.items()}
        env_sub = _make_env_sub(plan, env_in, slabs_q, k0)
        updates = jax.vmap(lambda i: program.body(i, env_sub))(ivec)
        ys: dict[str, Any] = {}
        carry, ys = _apply_chunk_updates(plan, updates, carry, ys, j, valid, shapes)
        return carry, ys

    if ch.local_chunks == 1:
        # Fast path: exactly one chunk per device — no lax.scan carry
        # threading and no dynamic window gather; the slab body runs
        # directly on the (statically sliced) single chunk.
        carry, ys = one_chunk(carry0, 0)
        ys = {k: v[None] for k, v in ys.items()}
        return carry, ys
    qs = jnp.arange(ch.local_chunks, dtype=jnp.int32)
    unroll = ch.local_chunks if unroll_chunks else 1
    return jax.lax.scan(one_chunk, carry0, qs, unroll=unroll)


def _execute_collective(dp: DistributedProgram, env: dict) -> dict:
    _maybe_fault("collective")
    plan, program, mesh = dp.plan, dp.program, dp.mesh
    axis = plan.axis
    t = plan.loop.trip_count

    repl_keys = [k for k in plan.context.env_keys
                 if plan.vars[k].in_strategy == "replicate"]
    env_repl = {k: env[k] for k in repl_keys}
    env_slab = {}
    with jax.named_scope("omp.entry"):
        for k in plan.sharded_in_keys:
            dec = plan.vars[k]
            if dec.in_strategy == "shard_halo":
                env_slab[k] = nest_mod.halo_slabs(env[k], plan.chunks,
                                                  dec.halo)
            else:
                env_slab[k] = nest_mod.pad_reshape(env[k], plan.chunks)

    aggregate = dp.comm_schedule == "aggregate"
    if dp.use_pallas:
        from repro.core import pallas_lower as plx

        pallas_interp = plx.resolve_interpret(dp.pallas_interpret, mesh)

    def device_fn(env_repl, env_slab):
        from repro.core import comm_schedule as cs_mod

        d = jax.lax.axis_index(axis)
        with jax.named_scope("omp.entry"):
            slab_stacks = {k: v[:, 0] for k, v in env_slab.items()}
        with jax.named_scope(f"omp.stage.{program.name}"), \
                indirect_scope(plan):
            if dp.use_pallas:
                carry, ys = plx.run_local_chunks_pallas(
                    plan, program, env_repl, slab_stacks, (d,),
                    interpret=pallas_interp, device=mesh.devices.flat[0])
            else:
                carry, ys = _run_local_chunks(plan, program, env_repl,
                                              slab_stacks, (d,),
                                              dp.unroll_chunks)

        # With the aggregate schedule, every psum-family combine of the
        # block (scatter buf+mask pairs, put broadcasts, reduction
        # partials) defers into ONE fused flat collective per
        # (collective, dtype) group instead of one launch per merge.
        outs: dict[str, Any] = {}
        pending: dict[tuple[str, str], tuple[str, Any]] = {}
        for key, dec in plan.vars.items():
            if dec.out_strategy in ("identity", "partial"):
                with jax.named_scope("omp.exit"):
                    outs[key] = ys[key][:, None]  # (n_loc, 1, c, *rest)
                continue
            with jax.named_scope(f"omp.combine.{key}"):
                if dec.out_strategy == "scatter":
                    buf, mask = carry[key]
                    if aggregate:
                        pending[(key, "buf")] = ("psum", buf)
                        pending[(key, "mask")] = ("psum",
                                                  mask.astype(jnp.int32))
                    else:
                        outs[key] = (
                            jax.lax.psum(buf, axis),
                            jax.lax.psum(mask.astype(jnp.int32), axis),
                        )
                elif dec.out_strategy == "put":
                    owner = plan.chunks.owner_of_last_iteration()
                    val = jnp.where(d == owner, carry[key],
                                    jnp.zeros_like(carry[key]))
                    if aggregate:
                        pending[(key, "put")] = ("psum", val)
                    else:
                        outs[key] = jax.lax.psum(val, axis)
                elif dec.out_strategy == "reduce":
                    rop = red_mod.get_reduction(dec.reduction_op)
                    if rop.collective == "gather":
                        outs[key] = carry[key][None]
                    elif aggregate:
                        pending[(key, "red")] = (rop.collective, carry[key])
                    else:
                        outs[key] = red_mod.cross_device_combine(
                            rop, carry[key], axis)
        if pending:
            with jax.named_scope("omp.combine"):
                combined = cs_mod.fused_collectives(pending, axis)
            for key, dec in plan.vars.items():
                if dec.out_strategy == "scatter":
                    outs[key] = (combined[(key, "buf")],
                                 combined[(key, "mask")])
                elif dec.out_strategy == "put":
                    outs[key] = combined[(key, "put")]
                elif dec.out_strategy == "reduce" \
                        and (key, "red") in combined:
                    outs[key] = combined[(key, "red")]
        return outs

    in_specs = (
        {k: P() for k in env_repl},
        {k: P(None, axis) for k in env_slab},
    )
    out_specs: dict[str, Any] = {}
    for key, dec in plan.vars.items():
        if dec.out_strategy in ("identity", "partial"):
            out_specs[key] = P(None, axis)
        elif dec.out_strategy == "scatter":
            out_specs[key] = (P(), P())
        elif dec.out_strategy == "put":
            out_specs[key] = P()
        elif dec.out_strategy == "reduce":
            rop = red_mod.get_reduction(dec.reduction_op)
            out_specs[key] = P(axis) if rop.collective == "gather" else P()
    if not out_specs:
        return dict(env)

    outs = shard_map(
        device_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
    )(env_repl, env_slab)

    # --- reassembly at the jit level (layout, not messages) ---------------
    result = dict(env)
    for key, dec in plan.vars.items():
        if dec.out_strategy in ("identity", "partial"):
            with jax.named_scope("omp.exit"):
                flat = nest_mod.unpad_flat(outs[key], plan.chunks, t)
                flat = flat.astype(env[key].dtype)
                if dec.out_strategy == "identity":
                    result[key] = flat
                else:
                    result[key] = jax.lax.dynamic_update_slice_in_dim(
                        env[key], flat, dec.write_map.b, 0)
            continue
        with jax.named_scope(f"omp.combine.{key}"):
            if dec.out_strategy == "scatter":
                summed, mask = outs[key]
                vmask = (mask > 0).reshape((-1,) + (1,) * (summed.ndim - 1))
                result[key] = jnp.where(vmask, summed.astype(env[key].dtype),
                                        env[key])
            elif dec.out_strategy == "put":
                result[key] = outs[key]
            elif dec.out_strategy == "reduce":
                rop = red_mod.get_reduction(dec.reduction_op)
                val = outs[key]
                if rop.collective == "gather":
                    val = rop.local_fold(val, 0)
                if key in env:
                    val = rop.pairwise(env[key], val)
                result[key] = val
    return result


# ---------------------------------------------------------------------------
# Rank-2 collective lowering (``collapse=2`` over a 2-D mesh)
# ---------------------------------------------------------------------------


def _axis_lane_vectors(ch, loop, j, c_dtype=jnp.int32):
    """One axis's lane vectors for global chunk ``j``: iteration numbers,
    validity mask and clamped loop indices (the per-axis analogue of
    ``_chunk_iteration_vectors``)."""
    ks = j * ch.chunk + jnp.arange(ch.chunk, dtype=c_dtype)
    valid = ks < loop.trip_count
    kc = jnp.minimum(ks, max(0, loop.trip_count - 1))
    ivec = loop.start + loop.step * kc
    return ks, valid, kc, ivec


def _make_env_sub2(plan, env_in, slab_stacks, q_pair, k0s):
    """Environment seen by the body inside one (chunk_i, chunk_j) pair."""
    qi, qj = q_pair
    env_sub: dict[str, Any] = {}
    for key in plan.context.env_keys:
        dec = plan.vars[key]
        info = plan.context.vars[key]
        if dec.in_strategy == "shard_halo":
            stacks = slab_stacks[key]
            if isinstance(qi, int):      # one-chunk fast path: static slice
                win = stacks[qi]
            else:
                win = jax.lax.dynamic_index_in_dim(stacks, qi, 0,
                                                   keepdims=False)
            offs = [k0s[0] + dec.halo_axes[0][0]]
            if dec.shard_ndim == 2:
                # stack dim for axis 1 is now position 1 (n_j)
                if isinstance(qj, int):
                    win = win[:, qj]
                else:
                    win = jax.lax.dynamic_index_in_dim(win, qj, 1,
                                                       keepdims=False)
                offs.append(k0s[1] + dec.halo_axes[1][0])
            env_sub[key] = ShiftedWindow(win, tuple(offs),
                                         info.shape, info.dtype)
        elif dec.in_strategy == "replicate":
            env_sub[key] = env_in[key]
        else:  # unused inside the body: placeholder, DCE'd by XLA
            env_sub[key] = jnp.zeros(info.shape, info.dtype)
    return env_sub


def _scan_local_chunks2(plan, program, env_in, slab_stacks, device_indices,
                        unroll_chunks=False):
    """Scan this device's (chunk_i, chunk_j) pairs, the body vmapped over
    each pair's lanes; the ``(carry, ys)`` of :func:`_run_local_chunks`."""
    ch_i, ch_j = plan.chunks_axes
    loop_i, loop_j = plan.nest.axes
    d_i, d_j = device_indices
    n_i, n_j = ch_i.local_chunks, ch_j.local_chunks
    tab_i, tab_j = _slot_table(ch_i), _slot_table(ch_j)

    carry0: dict[str, Any] = {}
    for key, dec in plan.vars.items():
        if dec.out_strategy == "reduce":
            rop = red_mod.get_reduction(dec.reduction_op)
            info = plan.context.vars[key]
            carry0[key] = red_mod.identity_like(
                rop, jnp.zeros(info.write.value_shape, info.write.value_dtype))

    def one_pair(carry, q):
        qi, qj = q // n_j, q % n_j
        ji = (tab_i[qi, d_i] if tab_i is not None
              else qi * ch_i.num_devices + d_i)
        jj = (tab_j[qj, d_j] if tab_j is not None
              else qj * ch_j.num_devices + d_j)
        _, valid_i, _, ivec = _axis_lane_vectors(ch_i, loop_i, ji)
        _, valid_j, _, jvec = _axis_lane_vectors(ch_j, loop_j, jj)
        env_sub = _make_env_sub2(plan, env_in, slab_stacks, (qi, qj),
                                 (ji * ch_i.chunk, jj * ch_j.chunk))
        updates = jax.vmap(
            lambda i: jax.vmap(lambda jv: program.body(i, jv, env_sub))(jvec)
        )(ivec)                                    # values (c_i, c_j, *rest)
        ys: dict[str, Any] = {}
        for key, dec in plan.vars.items():
            if dec.out_strategy in ("identity", "partial"):
                ys[key] = updates[key].value
            elif dec.out_strategy == "reduce":
                rop = red_mod.get_reduction(dec.reduction_op)
                upd = updates[key].value
                ident = red_mod.identity_like(rop, upd)
                vmask = (valid_i[:, None] & valid_j[None, :]).reshape(
                    (ch_i.chunk, ch_j.chunk) + (1,) * (upd.ndim - 2))
                part = rop.local_fold(jnp.where(vmask, upd, ident), (0, 1))
                carry[key] = rop.pairwise(carry[key], part)
        return carry, ys

    if n_i * n_j == 1:
        # Fast path: one (chunk_i, chunk_j) pair per device — no scan,
        # static window slicing (see _run_local_chunks).
        carry, ys = one_pair(dict(carry0), 0)
        ys = {k: v[None] for k, v in ys.items()}
    else:
        qs = jnp.arange(n_i * n_j, dtype=jnp.int32)
        unroll = n_i * n_j if unroll_chunks else 1
        carry, ys = jax.lax.scan(one_pair, carry0, qs, unroll=unroll)
    # (n_i*n_j, c_i, c_j, *rest) -> (n_i, c_i, n_j, c_j, *rest)
    ys = {k: jnp.moveaxis(v.reshape((n_i, n_j) + v.shape[1:]), 1, 2)
          for k, v in ys.items()}
    return carry, ys


def _execute_collective2(dp: DistributedProgram, env: dict) -> dict:
    _maybe_fault("collective2")
    plan, program, mesh = dp.plan, dp.program, dp.mesh
    ax_i, ax_j = plan.axes_names
    ch_i, ch_j = plan.chunks_axes
    trips = plan.nest.trip_counts

    repl_keys = [k for k in plan.context.env_keys
                 if plan.vars[k].in_strategy == "replicate"]
    env_repl = {k: env[k] for k in repl_keys}
    env_slab = {}
    slab_specs = {}
    with jax.named_scope("omp.entry"):
        for k in plan.sharded_in_keys:
            dec = plan.vars[k]
            if dec.shard_ndim == 2:
                env_slab[k] = nest_mod.halo_slabs2(
                    env[k], (ch_i, ch_j), dec.halo_axes)
                slab_specs[k] = P(None, ax_i, None, None, ax_j, None)
            else:
                env_slab[k] = nest_mod.halo_slabs(env[k], ch_i,
                                                  dec.halo_axes[0])
                slab_specs[k] = P(None, ax_i, None)

    aggregate = dp.comm_schedule == "aggregate"
    if dp.use_pallas:
        from repro.core import pallas_lower as plx

        pallas_interp = plx.resolve_interpret(dp.pallas_interpret, mesh)

    def device_fn(env_repl, env_slab):
        from repro.core import comm_schedule as cs_mod

        d_i = jax.lax.axis_index(ax_i)
        d_j = jax.lax.axis_index(ax_j)
        slab_stacks = {}
        with jax.named_scope("omp.entry"):
            for k, v in env_slab.items():
                if plan.vars[k].shard_ndim == 2:
                    # (n_i, w_i, n_j, w_j, *)
                    slab_stacks[k] = v[:, 0][:, :, :, 0]
                else:
                    slab_stacks[k] = v[:, 0]           # (n_i, w_i, *rest)
        with jax.named_scope(f"omp.stage.{program.name}"):
            if dp.use_pallas:
                carry, ys = plx.run_local_chunks_pallas(
                    plan, program, env_repl, slab_stacks, (d_i, d_j),
                    interpret=pallas_interp, device=mesh.devices.flat[0])
            else:
                carry, ys = _run_local_chunks(plan, program, env_repl,
                                              slab_stacks, (d_i, d_j),
                                              dp.unroll_chunks)
        outs: dict[str, Any] = {}
        reduce_items: dict[str, tuple] = {}
        for key, dec in plan.vars.items():
            if dec.out_strategy in ("identity", "partial"):
                # (n_i, c_i, n_j, c_j, *) -> (n_i, 1, c_i, n_j, 1, c_j, *)
                with jax.named_scope("omp.exit"):
                    outs[key] = ys[key][:, None, :, :, None]
            elif dec.out_strategy == "reduce":
                rop = red_mod.get_reduction(dec.reduction_op)
                if aggregate:
                    reduce_items[key] = (rop, carry[key])
                else:
                    with jax.named_scope(f"omp.combine.{key}"):
                        outs[key] = red_mod.cross_device_combine(
                            rop, carry[key], (ax_i, ax_j))
        if reduce_items:
            with jax.named_scope("omp.combine"):
                outs.update(cs_mod.fused_cross_device_combine(
                    reduce_items, (ax_i, ax_j)))
        return outs

    in_specs = ({k: P() for k in env_repl}, slab_specs)
    out_specs: dict[str, Any] = {}
    for key, dec in plan.vars.items():
        if dec.out_strategy in ("identity", "partial"):
            out_specs[key] = P(None, ax_i, None, None, ax_j, None)
        elif dec.out_strategy == "reduce":
            out_specs[key] = P()
    if not out_specs:
        return dict(env)

    outs = shard_map(
        device_fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
    )(env_repl, env_slab)

    # --- reassembly at the jit level (layout, not messages) ---------------
    result = dict(env)
    for key, dec in plan.vars.items():
        if dec.out_strategy == "identity":
            with jax.named_scope("omp.exit"):
                flat = nest_mod.unpad_flat2(outs[key], (ch_i, ch_j), trips)
                result[key] = flat.astype(env[key].dtype)
        elif dec.out_strategy == "partial":
            with jax.named_scope("omp.exit"):
                flat = nest_mod.unpad_flat2(outs[key], (ch_i, ch_j), trips)
                starts = (dec.write_maps[0].b, dec.write_maps[1].b) \
                    + (0,) * (flat.ndim - 2)
                result[key] = jax.lax.dynamic_update_slice(
                    env[key], flat.astype(env[key].dtype), starts)
        elif dec.out_strategy == "reduce":
            rop = red_mod.get_reduction(dec.reduction_op)
            val = outs[key]
            if key in env:
                with jax.named_scope(f"omp.combine.{key}"):
                    val = rop.pairwise(env[key], val)
            result[key] = val
    return result


# ---------------------------------------------------------------------------
# Master/worker lowering (paper-faithful baseline)
# ---------------------------------------------------------------------------


def _mw_send(x, src, dst, d, current, axis):
    """Point-to-point send emulation: ``dst`` receives ``x`` from ``src``."""
    msg = jax.lax.ppermute(x, axis, perm=[(src, dst)])
    return jnp.where(d == dst, msg, current)


def _execute_master_worker(dp: DistributedProgram, env: dict) -> dict:
    plan, program, mesh = dp.plan, dp.program, dp.mesh
    axis = plan.axis
    p_total = mesh.shape[axis]
    ch = plan.chunks
    w = ch.num_devices            # compute ranks (P-1 when master excluded)
    t = plan.loop.trip_count
    first_worker = p_total - w    # 1 when master excluded, else 0

    def device_fn(env_all):
        d = jax.lax.axis_index(axis)
        wd = jnp.clip(d - first_worker, 0, w - 1)

        # --- master -> worker sends of every IN buffer --------------------
        env_in: dict[str, Any] = {}
        slab_stacks: dict[str, Any] = {}
        with jax.named_scope("omp.entry"):
            for key in plan.context.env_keys:
                dec = plan.vars[key]
                info = plan.context.vars[key]
                if dec.in_strategy == "replicate":
                    x = env_all[key]
                    recv = x
                    for dst in range(first_worker, p_total):
                        if dst == 0:
                            continue
                        recv = _mw_send(x, 0, dst, d, recv, axis)
                    env_in[key] = recv
                elif dec.in_strategy == "shard":
                    x_pad = env_all[key]  # already (n_loc, W, c, *rest)
                    my = jnp.take(x_pad, wd, axis=1)
                    for dst_w in range(w):
                        dst = dst_w + first_worker
                        if dst == 0:
                            continue
                        slab = x_pad[:, dst_w]
                        my = _mw_send(slab, 0, dst, d, my, axis)
                    slab_stacks[key] = my
                else:
                    env_in[key] = jnp.zeros(info.shape, info.dtype)

        with jax.named_scope(f"omp.stage.{program.name}"):
            carry, ys = _scan_local_chunks(plan, program, env_in,
                                           slab_stacks, wd, dp.unroll_chunks)

        outs: dict[str, Any] = {}
        for key, dec in plan.vars.items():
            info = plan.context.vars[key]
            scope = ("omp.exit" if dec.out_strategy in ("identity", "partial")
                     else f"omp.combine.{key}")
            with jax.named_scope(scope):
                if dec.out_strategy in ("identity", "partial"):
                    # workers -> master sends of each slab stack, master
                    # assembles the padded buffer, then re-broadcasts it.
                    full = jnp.zeros((ch.padded_trip,) + info.shape[1:],
                                     info.dtype)
                    for src_w in range(w):
                        src = src_w + first_worker
                        stack = ys[key]  # (n_loc, c, *rest)
                        if src != 0:
                            got = jax.lax.ppermute(stack, axis,
                                                   perm=[(src, 0)])
                        else:
                            got = stack
                        rows = np.concatenate([
                            np.arange(ch.chunk) + (q * w + src_w) * ch.chunk
                            for q in range(ch.local_chunks)
                        ])
                        flat = got.reshape((-1,) + info.shape[1:])
                        placed = full.at[rows].set(flat)
                        full = jnp.where(d == 0, placed, full)
                    for dst in range(first_worker, p_total):
                        if dst == 0:
                            continue
                        full = _mw_send(full, 0, dst, d, full, axis)
                    outs[key] = full[None]
                elif dec.out_strategy == "scatter":
                    buf, mask = carry[key]
                    if first_worker == 1:
                        # The excluded master duplicated worker 0's chunks
                        # (clamped wd); drop its contribution before combining.
                        is_worker = (d >= 1).astype(buf.dtype)
                        buf = buf * is_worker.reshape((1,) * buf.ndim)
                        mask = jnp.logical_and(mask, d >= 1)
                    outs[key] = (
                        jax.lax.psum(buf, axis),
                        jax.lax.psum(mask.astype(jnp.int32), axis),
                    )
                elif dec.out_strategy == "put":
                    j_star = (t - 1) // ch.chunk
                    owner = j_star % w + first_worker
                    val = carry[key]
                    if owner != 0:
                        val = _mw_send(val, owner, 0, d, val, axis)
                    for dst in range(first_worker, p_total):
                        if dst == 0:
                            continue
                        val = _mw_send(val, 0, dst, d, val, axis)
                    outs[key] = val[None]
                elif dec.out_strategy == "reduce":
                    # Table 3: workers send partials; the master folds them in
                    # rank order into the identity-initialised accumulator.
                    rop = red_mod.get_reduction(dec.reduction_op)
                    acc = red_mod.identity_like(rop, carry[key])
                    for src_w in range(w):
                        src = src_w + first_worker
                        if src == 0:  # master computed its own chunks
                            acc = jnp.where(
                                d == 0, rop.pairwise(acc, carry[key]), acc)
                            continue
                        got = jax.lax.ppermute(carry[key], axis,
                                               perm=[(src, 0)])
                        acc = jnp.where(d == 0, rop.pairwise(acc, got), acc)
                    for dst in range(first_worker, p_total):
                        if dst == 0:
                            continue
                        acc = _mw_send(acc, 0, dst, d, acc, axis)
                    outs[key] = acc[None]
        return outs

    env_all = {}
    for key in plan.context.env_keys:
        dec = plan.vars[key]
        if dec.in_strategy == "shard":
            with jax.named_scope("omp.entry"):
                env_all[key] = nest_mod.pad_reshape(env[key], plan.chunks)
        else:
            env_all[key] = env[key]
    in_specs = {k: P() for k in env_all}
    out_specs: dict[str, Any] = {}
    for key, dec in plan.vars.items():
        if dec.out_strategy in ("identity", "partial", "put", "reduce"):
            out_specs[key] = P(axis)
        elif dec.out_strategy == "scatter":
            out_specs[key] = (P(), P())
    if not out_specs:
        return dict(env)

    outs = shard_map(
        device_fn, mesh=mesh, in_specs=(in_specs,), out_specs=out_specs,
    )(env_all)

    result = dict(env)
    for key, dec in plan.vars.items():
        scope = ("omp.exit" if dec.out_strategy in ("identity", "partial")
                 else f"omp.combine.{key}")
        with jax.named_scope(scope):
            if dec.out_strategy == "identity":
                result[key] = outs[key][0][:t]
            elif dec.out_strategy == "partial":
                flat = outs[key][0][:t]
                result[key] = jax.lax.dynamic_update_slice_in_dim(
                    env[key], flat.astype(env[key].dtype), dec.write_map.b, 0)
            elif dec.out_strategy == "scatter":
                summed, mask = outs[key]
                vmask = (mask > 0).reshape((-1,) + (1,) * (summed.ndim - 1))
                result[key] = jnp.where(
                    vmask, summed.astype(env[key].dtype), env[key])
            elif dec.out_strategy == "put":
                result[key] = outs[key][0]
            elif dec.out_strategy == "reduce":
                rop = red_mod.get_reduction(dec.reduction_op)
                val = outs[key][0]
                if key in env:
                    val = rop.pairwise(env[key], val)
                result[key] = val
    return result
