"""Pallas lowering — tiled shard-local kernels for chunk compute.

``Lowering.PALLAS`` keeps the whole distributed machinery of the
collective/fused lowerings — chunk-cyclic staging, halo exchanges, the
aggregated comm schedule, the jit-level reassembly — and swaps ONLY the
per-device chunk compute: instead of the lax chunk compute
(:func:`repro.core.transform._run_local_chunks`), each compute
span runs as one tiled :func:`pl.pallas_call` over this device's local
slab.  A *span* is a single loop stage, or — inside a fused region —
the maximal chain of consecutive loop stages between scheduled
exchanges that the ``comm_schedule`` hoist already isolates: those
stages share chunk geometry and only hand values to each other through
resident slabs, so the chain fuses into one kernel with intermediate
tiles forwarded in VMEM (never leaving the kernel).

Geometry (per axis) comes from the chunk-cyclic layout owned by
:mod:`repro.core.nest`: chunk ``j = q*P + d`` starts at ``k0 = j*c``;
its ``c`` lanes tile as :class:`~repro.core.nest.AxisTiles` (sublane
rounding per dtype, masked remainder lanes clamp to the last in-bounds
iteration exactly like the trip padding).  Each chunk's window (the
rows it reads, halo included) enters as one block indexed by the chunk,
with the chunk index squeezed away; halo windows overlap between chunks,
so halo-awareness lives in the static offset of each read
``x[i+b]`` inside the block (``b - b_min``) rather than in the BlockSpec.
Outputs leave as one block per tile.  Inside the kernel the body is
evaluated over the tile by :mod:`repro.core.tile_eval`, which serves
each window read as a static slice of the loaded block.

The kernel produces only dense per-lane body values; every merge
(scatter/put/reduce folds, slab state updates, cross-device combines)
runs outside on the sliced values via
:func:`~repro.core.tile_eval.merge_chunk_values`, which reproduces the
``(carry, ys)`` contract of ``_run_local_chunks`` bit-for-bit — that
is what lets the differential test wall pin the backend against the
lax lowering and the shared-memory reference.

Off-TPU (the CPU tests) the kernels run in interpret mode;
``Options(pallas_interpret=...)`` forces either mode, ``None`` picks
interpret off-TPU.  On a TPU every span is first compiled on its own
(:func:`_preflight`), so a span Mosaic refuses raises ``CompileError``.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import nest as nest_mod
from repro.core.nest import AxisTiles, derive_axis_tiles
from repro.core.tile_eval import (Src, eval_body, full, merge_chunk_values,
                                  trace_body, window_origin)
from repro.core.timing import timed_pass

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# The KernelPlan artifact (recorded on Compiled.passes, rendered by
# report.py)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelSpan:
    """One fused Pallas kernel: a chain of same-geometry loop stages
    with no exchange between them."""

    stage_names: tuple[str, ...]
    stage_indices: tuple[int, ...]
    rank: int
    grid: tuple[int, ...]
    tiles: tuple[AxisTiles, ...]
    forwarded: tuple[str, ...]      # keys forwarded tile-to-tile in VMEM
    n_outputs: int

    def describe(self) -> str:
        geo = " x ".join(
            f"{tl.n_tiles}*{tl.tile}" +
            (f" ({tl.masked_lanes} masked)" if tl.masked_lanes else "")
            for tl in self.tiles)
        line = (f"{'+'.join(self.stage_names)}: grid={self.grid} "
                f"tile={geo} chunk="
                + "x".join(str(tl.chunk) for tl in self.tiles))
        if self.forwarded:
            line += f"  vmem-forwarded: {', '.join(self.forwarded)}"
        return line


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """Tile geometry + fusion spans of a PALLAS-lowered program."""

    name: str
    rank: int
    spans: tuple[KernelSpan, ...]
    n_loop_stages: int

    @property
    def n_kernels(self) -> int:
        return len(self.spans)

    @property
    def max_fused(self) -> int:
        return max((len(s.stage_names) for s in self.spans), default=0)

    def describe_lines(self) -> list[str]:
        lines = [f"pallas kernels: {self.n_kernels} span(s) over "
                 f"{self.n_loop_stages} loop stage(s), interpret off-TPU"]
        for s in self.spans:
            lines.append("  " + s.describe())
        return lines


# ---------------------------------------------------------------------------
# Span planning
# ---------------------------------------------------------------------------


def _stage_geom(plan) -> tuple:
    return tuple((ch.chunk, ch.num_devices, ch.local_chunks,
                  ch.padded_trip, ch.trip_count)
                 for ch in plan.chunks_axes)


def _written_keys(plan) -> set:
    return {k for k, dec in plan.vars.items() if dec.out_strategy != "none"}


def compute_region_spans(rp) -> list[list[int]]:
    """Partition a region's executable loop stages into fusable spans.

    A stage joins the running span iff it shares chunk geometry, needs
    no gather/halo exchange, and every value it consumes is either
    external to the span or hand-off-able in VMEM (a resident feed from
    an in-span identity/partial producer).  Serial and zero-trip stages
    break spans (they never reach the kernel).
    """
    spans: list[list[int]] = []
    cur: list[int] | None = None
    cur_geom = None
    written: set = set()
    for si, se in enumerate(rp.stages):
        if se.kind != "loop" or se.plan is None \
                or se.plan.nest.total_trip == 0:
            cur = None
            continue
        plan = se.plan
        geom = _stage_geom(plan)
        ok = cur is not None and not se.gathers and geom == cur_geom
        if ok:
            for key in plan.context.env_keys:
                dec = plan.vars[key]
                if dec.in_strategy == "replicate":
                    if key in written:      # produced by a pending merge
                        ok = False
                        break
                elif dec.in_strategy in ("shard", "shard_halo"):
                    feed = se.feeds.get(key, "slice")
                    if feed == "halo":      # an exchange sits between
                        ok = False
                        break
                    if key in written:
                        if feed != "resident":
                            ok = False
                            break
                        if plan.rank == 2 \
                                and getattr(dec, "shard_ndim", 2) != 2:
                            ok = False      # 1-D slab of a 2-D nest:
                            break           # not lane-aligned in VMEM
        if ok:
            cur.append(si)
        else:
            cur = [si]
            spans.append(cur)
            cur_geom = geom
            written = set()
        written |= _written_keys(plan)
    return spans


def _span_dtype(plans) -> Any:
    """Tile-granularity dtype for a span: its first output's value
    dtype (geometry only — masked lanes, sublane rounding)."""
    for plan in plans:
        for key in sorted(plan.vars):
            dec = plan.vars[key]
            if dec.out_strategy != "none":
                return plan.context.vars[key].write.value_dtype
    return jnp.float32


def _span_meta(plans, names, indices) -> KernelSpan:
    plan0 = plans[0]
    dt = _span_dtype(plans)
    tiles = tuple(derive_axis_tiles(ch.chunk, dt)
                  for ch in plan0.chunks_axes)
    chs = plan0.chunks_axes
    if plan0.rank == 1:
        grid = (chs[0].local_chunks, tiles[0].n_tiles)
    else:
        grid = (chs[0].local_chunks, chs[1].local_chunks,
                tiles[0].n_tiles, tiles[1].n_tiles)
    # keys a later span stage consumes from an earlier one's tiles
    written: set = set()
    fwd: list[str] = []
    for pi, plan in enumerate(plans):
        if pi:
            for key in plan.context.env_keys:
                dec = plan.vars[key]
                if dec.in_strategy in ("shard", "shard_halo") \
                        and key in written and key not in fwd:
                    fwd.append(key)
        written |= _written_keys(plan)
    n_out = sum(len(_written_keys(p)) for p in plans)
    return KernelSpan(stage_names=tuple(names),
                      stage_indices=tuple(indices),
                      rank=plan0.rank, grid=grid, tiles=tiles,
                      forwarded=tuple(fwd), n_outputs=n_out)


@timed_pass("pallas")
def plan_block_kernel(plan, name: str | None = None) -> KernelPlan:
    """KernelPlan of a single ParallelFor block (one span)."""
    if plan.nest.total_trip == 0 or not _written_keys(plan):
        return KernelPlan(name=name or plan.name, rank=plan.rank,
                          spans=(), n_loop_stages=0)
    span = _span_meta([plan], [name or plan.name], [0])
    return KernelPlan(name=name or plan.name, rank=plan.rank,
                      spans=(span,), n_loop_stages=1)


@timed_pass("pallas")
def plan_region_kernels(rp) -> KernelPlan:
    """KernelPlan of a fused region: one span per exchange-free chain."""
    spans = []
    for idxs in compute_region_spans(rp):
        plans = [rp.stages[i].plan for i in idxs]
        names = [rp.stages[i].name for i in idxs]
        spans.append(_span_meta(plans, names, idxs))
    return KernelPlan(name=rp.name, rank=rp.rank, spans=tuple(spans),
                      n_loop_stages=sum(len(s.stage_indices)
                                        for s in spans))


# ---------------------------------------------------------------------------
# Kernel execution
# ---------------------------------------------------------------------------


def resolve_interpret(option, mesh) -> bool:
    """None -> interpret off-TPU (the CPU test path); True/False forces."""
    if option is not None:
        return bool(option)
    return mesh.devices.flat[0].platform != "tpu"


@dataclasses.dataclass
class SpanStage:
    """One stage's kernel-side feeds, assembled by the executor."""

    name: str
    plan: Any
    program: Any
    ext_windows: dict          # key -> local slab stacks (kernel input)
    env_repl: dict             # key -> replicated array (kernel input)
    forwarded: frozenset       # keys served from in-span producer tiles


# VMEM per TensorCore, by device kind (the figures of
# ``jax.experimental.pallas.tpu.get_tpu_info``).  A kernel asks for three
# quarters of it; other kinds keep the compiler's default scoped limit.
_VMEM_BYTES = {"TPU v5 lite": 128 << 20, "TPU v5e": 128 << 20,
               "TPU v6 lite": 128 << 20, "TPU v6e": 128 << 20,
               "TPU v5": 64 << 20, "TPU v5p": 64 << 20}


# ---------------------------------------------------------------------------
# Kernel inputs and outputs
#
# Mosaic wants the last two dims of every block divisible by (sublane,
# 128) or equal to the array's own, so each chunk's block is laid out with
# the chunk index squeezed away in front: windows as ``(n, 1, L)`` (rows
# of scalars) / ``(n, L, *rest)`` / ``(n_i, n_j, L_i, L_j, *rest)``,
# outputs as ``(n, 1, padded)`` / ``(n, padded, *v)`` /
# ``(n_i, n_j, padded_i, padded_j, *v)``.  ``derive_axis_tiles`` gives
# either one tile per chunk (the block spans the axis) or 256-lane tiles,
# so output blocks are always aligned.  A window is padded so every tile
# loads an aligned region: the whole window with one tile per chunk,
# otherwise ``tile + halo`` rows from an aligned dynamic start.
# ---------------------------------------------------------------------------


def _align(pos: int, ndim: int, dtype) -> int:
    """Alignment a dynamic start needs on block dim ``pos`` of ``ndim``."""
    if pos == ndim - 1:
        return 128
    if pos == ndim - 2:
        return nest_mod.sublane_for(dtype)
    return 1


def _axis_load(tl: AxisTiles, halo_w: int, align: int) -> tuple:
    """``(n_tiles, tile, align, rows loaded per tile)`` plus the padded
    window length for one sharded axis."""
    if tl.n_tiles == 1:
        length = tl.padded + halo_w
    else:
        length = tl.tile + -(-halo_w // align) * align
    return (tl.n_tiles, tl.tile, align, length), \
        (tl.n_tiles - 1) * tl.tile + length


@dataclasses.dataclass
class _Input:
    si: int
    key: str
    kind: str                  # "win" | "scalar" | "vec" | "arr"
    array: Any
    spec: Any
    loads: tuple = ()          # win: _axis_load per sharded axis
    lead: bool = False         # win: rows of scalars, laid out (n, 1, L)

    def load(self, ref, ts):
        if self.kind in ("scalar", "vec"):
            return ref[0]
        if self.kind == "arr":
            return ref[...]
        idx = [0] if self.lead else []
        for t, (n, tile, align, length) in zip(ts, self.loads):
            idx.append(slice(None) if n == 1 else
                       pl.ds(pl.multiple_of(t * tile, align), length))
        return ref[tuple(idx) + (slice(None),) * (len(ref.shape) - len(idx))]


def _window_input(si, key, arr, two_d: bool, rank: int, tiles) -> _Input:
    if two_d:                                # (n_i, w_i, n_j, w_j, *rest)
        rest = arr.shape[4:]
        pads = [(0, 0)] * arr.ndim
        loads = []
        for a, ax in ((0, 1), (1, 3)):
            ld, total = _axis_load(tiles[a], arr.shape[ax] - tiles[a].chunk,
                                   _align(a, 2 + len(rest), arr.dtype))
            pads[ax] = (0, total - arr.shape[ax])
            loads.append(ld)
        arr = jnp.moveaxis(jnp.pad(arr, pads), 2, 1)
        spec = pl.BlockSpec((None, None) + arr.shape[2:],
                            lambda qi, qj, ti, tj: (qi, qj)
                            + (0,) * (arr.ndim - 2))
        return _Input(si, key, "win", arr, spec, tuple(loads))
    rest = arr.shape[2:]                     # (n, w, *rest)
    lead = not rest
    ld, total = _axis_load(
        tiles[0], arr.shape[1] - tiles[0].chunk,
        _align(1 if lead else 0, 2 if lead else 1 + len(rest), arr.dtype))
    arr = jnp.pad(arr, [(0, 0), (0, total - arr.shape[1])]
                  + [(0, 0)] * len(rest))
    if lead:
        arr = arr.reshape(arr.shape[0], 1, total)
    zeros = (0,) * (arr.ndim - 1)
    imap = ((lambda q, t: (q,) + zeros) if rank == 1
            else (lambda qi, qj, ti, tj: (qi,) + zeros))
    return _Input(si, key, "win", arr, pl.BlockSpec((None,) + arr.shape[1:],
                                                    imap), (ld,), lead)


def _repl_input(si, key, arr) -> _Input:
    arr = jnp.asarray(arr)
    if arr.ndim == 0:
        return _Input(si, key, "scalar", arr.reshape(1),
                      pl.BlockSpec(memory_space=pltpu.SMEM))
    kind = "vec" if arr.ndim == 1 else "arr"
    if kind == "vec":
        arr = arr.reshape(1, -1)
    return _Input(si, key, kind, arr, pl.BlockSpec(
        arr.shape, lambda *_g: (0,) * arr.ndim))


def _collect_io(stages, rank: int, tiles):
    """Kernel inputs (after the SMEM meta scalars) and output
    shapes/specs, in stable order."""
    inputs = []
    for si, sp in enumerate(stages):
        for key in sorted(sp.ext_windows):
            two_d = rank == 2 and getattr(sp.plan.vars[key],
                                          "shard_ndim", 1) == 2
            inputs.append(_window_input(si, key, sp.ext_windows[key], two_d,
                                        rank, tiles))
        for key in sorted(sp.env_repl):
            inputs.append(_repl_input(si, key, sp.env_repl[key]))
    out_shapes, out_specs, out_keys = [], [], []
    for si, sp in enumerate(stages):
        plan = sp.plan
        chs = plan.chunks_axes
        for key in sorted(plan.vars):
            if plan.vars[key].out_strategy == "none":
                continue
            info = plan.context.vars[key]
            vshape = tuple(info.write.value_shape)
            vz = (0,) * len(vshape)
            if rank == 1 and not vshape:
                full = (chs[0].local_chunks, 1, tiles[0].padded)
                spec = pl.BlockSpec((None, 1, tiles[0].tile),
                                    lambda q, t: (q, 0, t))
            elif rank == 1:
                full = (chs[0].local_chunks, tiles[0].padded) + vshape
                spec = pl.BlockSpec((None, tiles[0].tile) + vshape,
                                    lambda q, t, vz=vz: (q, t) + vz)
            else:
                full = (chs[0].local_chunks, chs[1].local_chunks,
                        tiles[0].padded, tiles[1].padded) + vshape
                spec = pl.BlockSpec(
                    (None, None, tiles[0].tile, tiles[1].tile) + vshape,
                    lambda qi, qj, ti, tj, vz=vz: (qi, qj, ti, tj) + vz)
            out_shapes.append(jax.ShapeDtypeStruct(full,
                                                   info.write.value_dtype))
            out_specs.append(spec)
            out_keys.append((si, key))
    return inputs, out_shapes, out_specs, out_keys


# Lowered kernels (by module text) that already compiled for the chip.
_PREFLIGHT_OK: set = set()


def _preflight(call, args, names, device) -> None:
    """Compile a span's kernel on its own for ``device`` so that a kernel
    Mosaic refuses surfaces as a ``CompileError`` naming the span, at
    ``omp.compile`` time."""
    from jax.sharding import SingleDeviceSharding

    from repro.core.api import CompileError

    sharding = SingleDeviceSharding(device)
    avals = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
             for a in args]
    try:
        lowered = jax.jit(call).lower(*avals)
        text = lowered.as_text()
        if text not in _PREFLIGHT_OK:
            lowered.compile()
            _PREFLIGHT_OK.add(text)
    except Exception as e:
        reason = " ".join(str(e).split())[:1200]
        raise CompileError(
            f"Lowering.PALLAS: span {'+'.join(names)!r} does not compile "
            f"for {device.device_kind}: {type(e).__name__}: {reason}") from e


def execute_span(stages: list[SpanStage], device_indices: tuple,
                 interpret: bool, device=None) -> list[tuple[dict, dict]]:
    """Run a span's loop bodies as ONE tiled pallas_call; returns the
    ``(carry, ys)`` pair of every stage (the ``_run_local_chunks``
    contract), merges computed outside the kernel.  Compiled (not
    interpreted) spans are first compiled alone for ``device``."""
    plan0 = stages[0].plan
    rank = plan0.rank
    chs = plan0.chunks_axes
    dt = _span_dtype([sp.plan for sp in stages])
    tiles = tuple(derive_axis_tiles(ch.chunk, dt) for ch in chs)
    lanes = tuple(tl.tile for tl in tiles)

    inputs, out_shapes, out_specs, out_keys = _collect_io(stages, rank, tiles)
    if not out_keys:
        return [({}, {}) for _ in stages]
    bodies = [trace_body(sp.plan, sp.program) for sp in stages]
    out_index = {k: oi for oi, k in enumerate(out_keys)}
    meta = jnp.stack([jnp.asarray(d, jnp.int32) for d in device_indices])
    n_in = len(inputs)

    def kernel(*refs):
        meta_ref = refs[0]
        in_refs = refs[1:1 + n_in]
        out_refs = refs[1 + n_in:]
        qs = [pl.program_id(a) for a in range(rank)]
        ts = [pl.program_id(rank + a) for a in range(rank)]
        # masked remainder lanes clamp to the last in-bounds iteration,
        # exactly like the chunk-cyclic trip padding
        ivals = []
        for a, (ch, tl, loop) in enumerate(zip(chs, tiles, plan0.nest.axes)):
            k0 = (qs[a] * ch.num_devices + meta_ref[a]) * ch.chunk \
                + ts[a] * tl.tile
            ks = k0 + (jax.lax.iota(jnp.int32, tl.tile) if rank == 1 else
                       jax.lax.broadcasted_iota(jnp.int32, lanes, a))
            kc = jnp.minimum(ks, max(0, loop.trip_count - 1))
            ivals.append((loop.start + loop.step * kc, (True,) * rank))
        loaded = {(inp.si, inp.key): inp.load(ref, ts)
                  for inp, ref in zip(inputs, in_refs)}

        span_vals: dict[str, Any] = {}
        for si, (sp, body) in enumerate(zip(stages, bodies)):
            plan = sp.plan
            srcs = {}
            for key in body.env_keys:
                dec = plan.vars[key]
                if dec.in_strategy in ("shard", "shard_halo"):
                    r = dec.shard_ndim if rank == 2 else 1
                    srcs[key] = Src(
                        "win", span_vals[key] if key in sp.forwarded
                        else loaded[(si, key)],
                        tuple(window_origin(dec, a) for a in range(r)))
                elif dec.in_strategy == "replicate":
                    srcs[key] = Src("val", loaded[(si, key)])
                else:
                    srcs[key] = Src("zeros", info=plan.context.vars[key])
            keys_out = [k for (osi, k) in out_keys if osi == si]
            got = eval_body(body, plan, ivals, srcs, lanes, keys_out)
            for key in keys_out:
                oi = out_index[(si, key)]
                v = full(*got[key], lanes).astype(out_shapes[oi].dtype)
                out_refs[oi][...] = v[None] if v.ndim == 1 else v
                if plan.vars[key].out_strategy in ("identity", "partial"):
                    span_vals[key] = v

    grid = tuple(ch.local_chunks for ch in chs) \
        + tuple(tl.n_tiles for tl in tiles)
    names = [sp.name for sp in stages]
    params = {}
    if not interpret and device.device_kind in _VMEM_BYTES:
        params["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_BYTES[device.device_kind] * 3 // 4)
    call = pl.pallas_call(
        kernel, grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [inp.spec for inp in inputs],
        out_specs=out_specs, out_shape=out_shapes,
        interpret=interpret, name="omp_" + "_".join(names), **params)
    args = [meta] + [inp.array for inp in inputs]
    if not interpret:
        _preflight(call, args, names, device)
    with jax.named_scope("omp.kernel." + "_".join(names)):
        outs = call(*args)
    if not isinstance(outs, (list, tuple)):
        outs = [outs]

    results = []
    for si, sp in enumerate(stages):
        vals = {}
        for oi, (osi, key) in enumerate(out_keys):
            if osi != si:
                continue
            v = outs[oi]
            if rank == 1:
                if not sp.plan.context.vars[key].write.value_shape:
                    v = v[:, 0]                 # (n, 1, padded) rows
                vals[key] = v[:, :tiles[0].chunk]
            else:
                vals[key] = jnp.moveaxis(v, 1, 2)[
                    :, :tiles[0].chunk, :, :tiles[1].chunk]
        results.append(merge_chunk_values(sp.plan, vals, device_indices))
    return results


def run_local_chunks_pallas(plan, program, env_in, slab_stacks,
                            device_indices, *, interpret: bool, device=None):
    """Drop-in for ``transform._run_local_chunks`` backed by one
    pallas_call over this device's slab."""
    sp = SpanStage(name=plan.name, plan=plan, program=program,
                   ext_windows=slab_stacks, env_repl=env_in,
                   forwarded=frozenset())
    (carry, ys), = execute_span([sp], tuple(device_indices), interpret,
                                device)
    return carry, ys
