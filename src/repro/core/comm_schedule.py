"""Region-wide communication scheduling (the ``schedule_comm`` pass).

The paper closes by calling its generated MPI "a starting point that
still can be further optimized by software engineers"; the single most
standard such optimization is **message aggregation and communication/
computation overlap**.  The cost-modeled planner (:mod:`repro.core.comm`)
decides *what* each boundary moves; this pass decides *how the region
moves it*: it builds a region-wide DAG of the planned exchanges and

* **aggregates** — every buffer crossing the same (mesh-axis, shift)
  boundary at the same issue point is packed into ONE ``ppermute``
  payload per ring direction (pack → single collective → unpack; mixed
  dtypes and unequal halo widths ride a byte-level concat through
  ``lax.bitcast_convert_type``), so k same-boundary exchanges cost one
  launch instead of k;
* **fuses** — the per-stage cross-device reduction combines (``psum`` /
  ``pmax`` / ``pmin`` partials, scatter buf+mask pairs, ``put``
  broadcasts) concatenate their flattened operands per (collective,
  dtype) group and cross the mesh in one collective call (this JAX
  lowers a *tuple* ``psum`` to one all-reduce per leaf, so the fusion
  must be an explicit concat — verified bit-identical);
* **hoists** — each exchange is issued at the earliest stage after its
  producer, so fused regions *prefetch* halos while the intervening
  stages compute (XLA overlaps the in-flight collective with the
  compute between producer and consumer).

The pass sits between **plan_comm** and **lower** in the
:func:`repro.core.api.compile` pipeline, is recorded as a first-class
artifact (:class:`CommSchedule` on ``Compiled.passes``), and is toggled
by ``Options(comm_schedule="aggregate"|"inline")`` — ``inline`` pins
the PR 4 per-buffer behavior for measurement.  Wire bytes are identical
in both modes (packing concatenates, it never pads); what changes is
the *launch* count, which the aggregated cost model prices at
:data:`repro.core.comm.ALPHA_LAUNCH_BYTES` byte-equivalents per launch.

The executors (:func:`repro.core.region._execute_region` /
``_execute_region2`` and the collective lowerings in
:mod:`repro.core.transform`) consume the schedule instead of emitting
per-buffer rings inline; the packing emitters below delegate to
:func:`repro.core.comm.halo_exchange` for single-buffer groups so a
lone boundary never pays pack/unpack overhead.
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict, deque
from typing import Any

import jax
import jax.numpy as jnp

from repro.core import comm as comm_mod
from repro.core import reduction as red_mod
from repro.core.timing import timed_pass

SCHEDULE_MODES = ("aggregate", "inline")

_FUSABLE = ("psum", "pmax", "pmin")


# ---------------------------------------------------------------------------
# Schedule IR
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CommEvent:
    """One planned halo exchange, placed in the stage timeline.

    ``shifts`` is per-axis ``(delta_min, delta_max)`` relative to the
    producing slab's base — exactly what the ring emitters consume —
    and ``producer_idx``/``consumer_idx`` index ``RegionPlan.stages``.
    The event is *issued* right after its producer (the hoist) and
    consumed at ``consumer_idx``.
    """

    key: str
    consumer: str
    consumer_idx: int
    producer: str
    producer_idx: int
    rank: int
    shifts: tuple                  # per-axis (delta_min, delta_max)
    chunks: tuple                  # per-axis chunk sizes
    num_devices: tuple             # per-axis ring sizes
    wire_bytes: int
    hops: int                      # inline ppermute launches

    @property
    def span(self) -> int:
        """Stages of compute the prefetch can overlap with."""
        return self.consumer_idx - self.producer_idx - 1


@dataclasses.dataclass(frozen=True)
class CommGroup:
    """Events packed into one exchange, issued after ``issue_idx``."""

    issue_idx: int
    issue_stage: str
    events: tuple[CommEvent, ...]
    launches_inline: int
    launches_packed: int

    @property
    def keys(self) -> tuple[str, ...]:
        return tuple(ev.key for ev in self.events)


@dataclasses.dataclass(frozen=True)
class ReduceFusion:
    """Per-stage fusion of cross-device combines into flat collectives."""

    stage: str
    stage_idx: int
    paths: tuple[str, ...]         # key (or key.mask) per combine operand
    launches_inline: int
    launches_fused: int            # one per (collective, dtype) group


@dataclasses.dataclass
class CommSchedule:
    """The schedule_comm artifact: the event timeline plus the launch
    accounting before/after aggregation."""

    mode: str
    rank: int
    events: tuple[CommEvent, ...]
    groups: tuple[CommGroup, ...]          # empty in inline mode
    reduce_fusions: tuple[ReduceFusion, ...]
    launches_inline: int
    launches_scheduled: int
    wire_bytes: int
    n_hoisted: int = 0                     # events with span >= 1

    def __post_init__(self) -> None:
        self._by_issue: dict[int, list[CommGroup]] = defaultdict(list)
        for g in self.groups:
            self._by_issue[g.issue_idx].append(g)

    def groups_after(self, stage_idx: int) -> list[CommGroup]:
        """Groups to issue right after ``stage_idx`` executes."""
        return self._by_issue.get(stage_idx, [])

    @property
    def launches_saved(self) -> int:
        return self.launches_inline - self.launches_scheduled

    def modeled_cost_bytes(self) -> tuple[int, int]:
        """(inline, scheduled) alpha-model costs in byte equivalents."""
        return (comm_mod.modeled_cost_bytes(self.wire_bytes,
                                            self.launches_inline),
                comm_mod.modeled_cost_bytes(self.wire_bytes,
                                            self.launches_scheduled))

    def describe_lines(self) -> list[str]:
        lines = []
        for g in self.groups:
            dests = ", ".join(
                f"{ev.key!r}->{ev.consumer}"
                + (f" (+{ev.span} stage overlap)" if ev.span else "")
                for ev in g.events)
            lines.append(
                f"after {g.issue_stage}: pack [{dests}] -> "
                f"{g.launches_packed} ppermute launch(es) "
                f"(inline: {g.launches_inline})")
        for rf in self.reduce_fusions:
            lines.append(
                f"{rf.stage}: fuse {rf.launches_inline} combine(s) "
                f"{list(rf.paths)} -> {rf.launches_fused} collective "
                "call(s)")
        before, after = self.modeled_cost_bytes()
        lines.append(
            f"collective launches: {self.launches_inline} inline -> "
            f"{self.launches_scheduled} scheduled "
            f"(alpha={comm_mod.ALPHA_LAUNCH_BYTES} B/launch: "
            f"~{before} -> ~{after} B-equiv)")
        return lines


# ---------------------------------------------------------------------------
# Building the schedule from a RegionPlan
# ---------------------------------------------------------------------------


def _packed_launches(events, rank: int) -> int:
    """Ring launches of one packed group: one per used direction per
    axis (the per-buffer payloads concat into one array each)."""
    n = 0
    for d in range(rank):
        if any(max(0, -ev.shifts[d][0]) > 0 for ev in events):
            n += 1
        if any(max(0, ev.shifts[d][1]) > 0 for ev in events):
            n += 1
    return n


def _stage_combines(plan, rank: int) -> list[tuple[str, str, str]]:
    """(path, collective, dtype) per cross-device combine the stage's
    output merge will issue — the fusable all-reduce population."""
    out: list[tuple[str, str, str]] = []
    for key, dec in plan.vars.items():
        if dec.out_strategy == "reduce":
            rop = red_mod.get_reduction(dec.reduction_op)
            if rop.collective in _FUSABLE:
                info = plan.context.vars[key]
                out.append((key, rop.collective,
                            str(info.write.value_dtype)))
        elif dec.out_strategy == "scatter" and rank == 1:
            info = plan.context.vars[key]
            out.append((key, "psum", str(info.dtype)))
            out.append((key + ".mask", "psum", "int32"))
        elif dec.out_strategy == "put" and rank == 1:
            info = plan.context.vars[key]
            out.append((key, "psum", str(info.dtype)))
    return out


@timed_pass("schedule_comm")
def build_comm_schedule(rp, *, mode: str = "aggregate") -> CommSchedule:
    """Schedule a planned region's communication: the **schedule_comm**
    pass.  Walks ``rp.stages`` in order, pairing every ``halo`` feed
    with its :class:`~repro.core.comm.BoundaryComm`, tracking the last
    slab writer per key (the producer), and — in ``"aggregate"`` mode —
    grouping events by issue point and fusing per-stage reduction
    combines.  ``"inline"`` records the same events with no groups (the
    PR 4 per-buffer baseline, kept measurable)."""
    if mode not in SCHEDULE_MODES:
        raise ValueError(
            f"unknown comm schedule mode {mode!r}; expected {SCHEDULE_MODES}")
    rank = rp.rank
    pending: dict[tuple[str, str], deque] = defaultdict(deque)
    for bc in rp.comms:
        if bc.op == comm_mod.HALO:
            pending[(bc.stage, bc.key)].append(bc)

    events: list[CommEvent] = []
    reduce_fusions: list[ReduceFusion] = []
    reduce_inline = reduce_fused = 0
    last_writer: dict[str, tuple[int, str]] = {}
    for si, se in enumerate(rp.stages):
        if se.kind != "loop" or se.plan is None:
            continue
        plan = se.plan
        if plan.nest.total_trip == 0:
            continue
        for key, feed in se.feeds.items():
            if feed != "halo":
                continue
            bc = pending[(se.name, key)].popleft()
            prod_idx, prod_name = last_writer[key]
            if rank == 2:
                chunks = tuple(c.chunk for c in plan.chunks_axes)
                nd = tuple(c.num_devices for c in plan.chunks_axes)
                shifts = tuple(bc.shift)
            else:
                chunks = (plan.chunks.chunk,)
                nd = (plan.chunks.num_devices,)
                shifts = (bc.shift,)
            events.append(CommEvent(
                key=key, consumer=se.name, consumer_idx=si,
                producer=prod_name, producer_idx=prod_idx, rank=rank,
                shifts=shifts, chunks=chunks, num_devices=nd,
                wire_bytes=bc.cost.wire_bytes, hops=bc.cost.hops))

        combines = _stage_combines(plan, rank)
        if combines:
            kinds = {(c, dt) for _, c, dt in combines}
            reduce_inline += len(combines)
            reduce_fused += len(kinds)
            if len(combines) > len(kinds):
                reduce_fusions.append(ReduceFusion(
                    stage=se.name, stage_idx=si,
                    paths=tuple(p for p, _, _ in combines),
                    launches_inline=len(combines),
                    launches_fused=len(kinds)))

        for key, dec in plan.vars.items():
            if dec.out_strategy in ("identity", "partial"):
                last_writer[key] = (si, se.name)

    halo_inline = sum(ev.hops for ev in events)
    groups: list[CommGroup] = []
    if mode == "aggregate":
        by_issue: dict[int, list[CommEvent]] = defaultdict(list)
        for ev in events:
            by_issue[ev.producer_idx].append(ev)
        for idx in sorted(by_issue):
            evs = tuple(by_issue[idx])
            groups.append(CommGroup(
                issue_idx=idx, issue_stage=evs[0].producer, events=evs,
                launches_inline=sum(ev.hops for ev in evs),
                launches_packed=_packed_launches(evs, rank)))
        halo_sched = sum(g.launches_packed for g in groups)
        red_sched = reduce_fused
    else:
        halo_sched = halo_inline
        red_sched = reduce_inline

    return CommSchedule(
        mode=mode, rank=rank, events=tuple(events), groups=tuple(groups),
        reduce_fusions=tuple(reduce_fusions) if mode == "aggregate" else (),
        launches_inline=halo_inline + reduce_inline,
        launches_scheduled=halo_sched + red_sched,
        wire_bytes=sum(ev.wire_bytes for ev in events),
        n_hoisted=sum(1 for ev in events if ev.span >= 1),
    )


# ---------------------------------------------------------------------------
# Byte-level payload packing
# ---------------------------------------------------------------------------


def pack_payloads(arrs) -> tuple[Any, tuple]:
    """Flatten arbitrary-dtype arrays into one ``uint8`` vector.

    Mixed dtypes and shapes concat byte-level through
    ``lax.bitcast_convert_type`` (bools ride as ``uint8``); the returned
    specs drive :func:`unpack_payloads` on the receiving side.
    """
    flats, specs = [], []
    for a in arrs:
        was_bool = a.dtype == jnp.bool_
        if was_bool:
            a = a.astype(jnp.uint8)
        itemsize = jnp.dtype(a.dtype).itemsize
        b = (a if a.dtype == jnp.uint8
             else jax.lax.bitcast_convert_type(a, jnp.uint8))
        flats.append(b.reshape(-1))
        nbytes = itemsize
        for s in a.shape:
            nbytes *= int(s)
        specs.append((tuple(a.shape), a.dtype, was_bool, nbytes))
    return jnp.concatenate(flats), tuple(specs)


def unpack_payloads(flat, specs) -> list:
    """Invert :func:`pack_payloads` (static offsets, no copies beyond
    the reshape/bitcast)."""
    outs, off = [], 0
    for shape, dtype, was_bool, nbytes in specs:
        seg = flat[off:off + nbytes]
        off += nbytes
        itemsize = jnp.dtype(dtype).itemsize
        if itemsize == 1:
            a = jax.lax.bitcast_convert_type(seg.reshape(shape), dtype)
        else:
            a = jax.lax.bitcast_convert_type(
                seg.reshape(shape + (itemsize,)), dtype)
        outs.append(a.astype(jnp.bool_) if was_bool else a)
    return outs


def _packed_ppermute(payloads, axis: str, perm):
    """One ring shift for many buffers: single-buffer groups go direct
    (no pack/unpack overhead); larger groups byte-pack into ONE
    ``ppermute``."""
    payloads = list(payloads)
    if len(payloads) == 1:
        return [jax.lax.ppermute(payloads[0], axis, perm=perm)]
    flat, specs = pack_payloads(payloads)
    recv = jax.lax.ppermute(flat, axis, perm=perm)
    return unpack_payloads(recv, specs)


def _ring_extend_many(entries, *, axis: str, num_devices: int, device_index,
                      stack_dim: int = 0, lane_dim: int = 1):
    """Widen many chunk-cyclic slabs at once with ONE packed ``ppermute``
    per ring direction — the aggregated
    :func:`repro.core.comm._ring_extend` (same chunk adjacency, same
    per-buffer roll corrections, byte-identical windows).

    ``entries``: ``(stacks, chunk, delta_min, delta_max)`` per buffer;
    halo widths may differ per buffer (unequal payload rows simply pack
    to different byte spans).
    """
    p = num_devices
    xs, metas = [], []
    for stacks, c, dmin, dmax in entries:
        left, right = max(0, -dmin), max(0, dmax)
        if left > c or right > c:
            raise ValueError(
                f"halo shift ({dmin}, {dmax}) exceeds one chunk (chunk={c});"
                " the planner should have chosen a gather")
        xs.append(jnp.moveaxis(stacks, (stack_dim, lane_dim), (0, 1)))
        metas.append((c, dmin, dmax, left, right))

    left_ids = [k for k, m in enumerate(metas) if m[3]]
    right_ids = [k for k, m in enumerate(metas) if m[4]]
    left_recv: dict[int, Any] = {}
    if left_ids:
        recvs = _packed_ppermute(
            [xs[k][:, metas[k][0] - metas[k][3]:] for k in left_ids],
            axis, perm=[((i - 1) % p, i) for i in range(p)])
        for k, recv in zip(left_ids, recvs):
            # device 0's chunk j-1 is the last device's PREVIOUS local chunk
            rolled = jnp.concatenate([recv[:1], recv[:-1]], axis=0)
            left_recv[k] = jnp.where(device_index == 0, rolled, recv)
    right_recv: dict[int, Any] = {}
    if right_ids:
        recvs = _packed_ppermute(
            [xs[k][:, :metas[k][4]] for k in right_ids],
            axis, perm=[((i + 1) % p, i) for i in range(p)])
        for k, recv in zip(right_ids, recvs):
            # the last device's chunk j+1 is device 0's NEXT local chunk
            rolled = jnp.concatenate([recv[1:], recv[-1:]], axis=0)
            right_recv[k] = jnp.where(device_index == p - 1, rolled, recv)

    outs = []
    for k, x in enumerate(xs):
        c, dmin, dmax, left, right = metas[k]
        parts = []
        if left:
            parts.append(left_recv[k])
        parts.append(x[:, max(0, dmin):c + min(0, dmax)])
        if right:
            parts.append(right_recv[k])
        win = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        outs.append(jnp.moveaxis(win, (0, 1), (stack_dim, lane_dim)))
    return outs


# ---------------------------------------------------------------------------
# Aggregated exchange emitters (run inside the fused shard_map)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class HaloItem:
    """Runtime payload of one scheduled exchange: the resident slab plus
    the static geometry the prior-patch needs (per-axis tuples; rank-1
    items use 1-tuples)."""

    stacks: Any
    chunks: tuple
    shifts: tuple
    prior: Any = None
    bases: tuple = (0,)
    covers: tuple | None = None
    dtype: Any = None


def aggregated_halo_exchange(items, *, axis: str, num_devices: int,
                             device_index) -> list:
    """Rank-1 aggregated exchange: every item's left payloads pack into
    one ``ppermute``, every right payload into another; returns one
    read window per item, byte-identical to per-buffer
    :func:`repro.core.comm.halo_exchange`.  Single-item groups delegate
    to it outright (no pack/unpack on lone boundaries)."""
    if len(items) == 1:
        it = items[0]
        return [comm_mod.halo_exchange(
            it.stacks, axis=axis, num_devices=num_devices,
            device_index=device_index, chunk=it.chunks[0],
            delta_min=it.shifts[0][0], delta_max=it.shifts[0][1],
            prior=it.prior, base=it.bases[0],
            cover=None if it.covers is None else it.covers[0],
            dtype=it.dtype)]
    wins = _ring_extend_many(
        [(it.stacks, it.chunks[0], it.shifts[0][0], it.shifts[0][1])
         for it in items],
        axis=axis, num_devices=num_devices, device_index=device_index)
    return [
        comm_mod.patch_window_prior(
            win, num_devices=num_devices, device_index=device_index,
            chunk=it.chunks[0], delta_min=it.shifts[0][0], prior=it.prior,
            base=it.bases[0],
            cover=None if it.covers is None else it.covers[0],
            dtype=it.dtype)
        for win, it in zip(wins, items)]


def aggregated_halo_exchange2(items, *, axes, num_devices,
                              device_indices) -> list:
    """Rank-2 aggregated exchange: one packed row-ring pass for every
    item, then one packed column-ring pass over the *extended* windows
    — the corner cells ride the second pass exactly as in the
    per-buffer emitter (:func:`repro.core.comm.halo_exchange2`), so a
    group of 2-D stencils costs at most 4 launches total."""
    if len(items) == 1:
        it = items[0]
        return [comm_mod.halo_exchange2(
            it.stacks, axes=axes, num_devices=num_devices,
            device_indices=device_indices, chunks=it.chunks,
            deltas=it.shifts, prior=it.prior, bases=it.bases,
            covers=it.covers, dtype=it.dtype)]
    wins = _ring_extend_many(
        [(it.stacks, it.chunks[0], it.shifts[0][0], it.shifts[0][1])
         for it in items],
        axis=axes[0], num_devices=num_devices[0],
        device_index=device_indices[0], stack_dim=0, lane_dim=1)
    wins = _ring_extend_many(
        [(win, it.chunks[1], it.shifts[1][0], it.shifts[1][1])
         for win, it in zip(wins, items)],
        axis=axes[1], num_devices=num_devices[1],
        device_index=device_indices[1], stack_dim=2, lane_dim=3)
    return [
        comm_mod.patch_window_prior2(
            win, num_devices=num_devices, device_indices=device_indices,
            chunks=it.chunks, deltas=it.shifts, prior=it.prior,
            bases=it.bases, covers=it.covers, dtype=it.dtype)
        for win, it in zip(wins, items)]


# ---------------------------------------------------------------------------
# Fused reduction combines
# ---------------------------------------------------------------------------

_COLLECTIVE_FNS = {
    "psum": jax.lax.psum,
    "pmax": jax.lax.pmax,
    "pmin": jax.lax.pmin,
}


def fused_collectives(entries, axis_name):
    """Cross the mesh once per (collective, dtype) group.

    ``entries``: ``{path: (collective, value)}`` with collective in
    psum/pmax/pmin.  Same-group operands flatten and concatenate into
    one vector — a single all-reduce launch — then split back (this JAX
    emits one all-reduce per *leaf* of a tuple ``psum``, so the concat
    is what actually merges launches).  Elementwise combines commute
    with concatenation, so results are bit-identical to per-operand
    collectives.  Returns ``{path: combined}``.
    """
    out: dict[Any, Any] = {}
    groups: dict[tuple[str, str], list] = {}
    for path, (coll, val) in entries.items():
        groups.setdefault((coll, str(jnp.result_type(val))), []).append(
            (path, jnp.asarray(val)))
    for (coll, _), members in groups.items():
        fn = _COLLECTIVE_FNS[coll]
        if len(members) == 1:
            path, val = members[0]
            out[path] = fn(val, axis_name)
            continue
        flats = [v.reshape(-1) for _, v in members]
        combined = fn(jnp.concatenate(flats), axis_name)
        off = 0
        for (path, val), flat in zip(members, flats):
            n = flat.shape[0]
            out[path] = combined[off:off + n].reshape(val.shape)
            off += n
    return out


def fused_cross_device_combine(items, axis_name):
    """Fused :func:`repro.core.reduction.cross_device_combine` over many
    reduction outputs at once: psum/pmax/pmin partials group through
    :func:`fused_collectives`; gather-style ops (``*``, ``/``) keep
    their per-key all-gather fold.  ``items``: ``{key: (ReductionOp,
    partial)}``; returns ``{key: combined}``."""
    out = {}
    entries = {}
    for key, (rop, val) in items.items():
        if rop.collective in _FUSABLE:
            entries[key] = (rop.collective, val)
        else:
            out[key] = red_mod.cross_device_combine(rop, val, axis_name)
    out.update(fused_collectives(entries, axis_name))
    return out
