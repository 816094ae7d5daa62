"""Cost-modeled communication planning for inter-loop boundaries.

The paper's §3.1.4 moves whole arrays through rank 0 at every loop
boundary (``MPI_Send``/``MPI_Recv`` of each block's data); the region
residency planner (:mod:`repro.core.region`) already reduces that to one
``all_gather`` per layout-incompatible boundary.  This module goes one
step further, in the direction real MPI ports take (MPI-rical, arXiv
2305.09438: stencil codes overwhelmingly use *neighbor* sends) and picks
the boundary operator by an explicit cost model rather than a fixed rule
(the OMP2HMPP idea, arXiv 1506.02833): every slab→consumer handoff is
lowered to the cheapest of four strategies

==============  =========================================================
op              when / what moves
==============  =========================================================
``resident``    producer OUT layout equals consumer IN layout: nothing
                moves (the residency elision of PR 1)
``halo``        consumer is a chunk-sharded (possibly stencil) read whose
                window only leaks ``L`` rows into the previous chunk and
                ``R`` rows into the next one: two ``jax.lax.ppermute``
                ring shifts move O(halo · chunks) rows instead of O(N)
``all_gather``  chunk-sharded consumer whose window cannot be served by
                neighbor shifts (or where the shifts would move more
                bytes than the gather): one ring ``all_gather``, then a
                local re-slice
``replicate``   the consumer semantically needs the full buffer on every
                rank (whole-array read, serial glue, out-merge priors):
                the ``all_gather`` is forced, not chosen
==============  =========================================================

Each decision is a :class:`BoundaryComm` carrying a :class:`CommCost`
(op, payload bytes per device, modeled total wire bytes, ring hop count)
plus the costs of the rejected alternatives — the transformation report
(:func:`repro.core.report.render_region`) prints them per boundary.

The halo *emitters* live here (:func:`halo_exchange` for rank-1 slabs,
:func:`halo_exchange2` for rank-2: row-ring then column-ring shifts,
corners riding the second pass); the shared slab-window geometry they
build against is owned by the loop-nest IR (:mod:`repro.core.nest`,
re-exported here) so the per-loop staging path
(:mod:`repro.core.transform`), the fused region path and this cost
model all address byte-identical read windows.  Rank-2 boundaries plan
through :func:`plan_boundary2` over :class:`SlabLayout2` with per-axis
halo windows, cost-modeled against the padded-slab all-gather exactly
as the 1-D rule below.

Window geometry (all in k-space, ``0 <= b_min <= b_max`` guaranteed by
:mod:`repro.core.plan` eligibility): consumer chunk ``j`` reads positions
``[j*c + b_min, (j+1)*c - 1 + b_max]``.  Relative to a producer slab
based at ``base`` the offsets are ``delta = b - base``; rows below the
chunk's own slab rows come from the *previous* chunk's tail
(``L = max(0, -delta_min)`` rows), rows above from the *next* chunk's
head (``R = max(0, delta_max)`` rows).  Rows outside the slab's cover
``[0, cover)`` are patched from the replicated prior copy (partial-write
producers keep one — the MPI analogue is the unmodified boundary rows
every rank already owns).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Mapping

import jax
import jax.numpy as jnp

# The window geometry is owned by the loop-nest IR (repro.core.nest) —
# re-exported here so the cost model and its tests address one name; the
# per-loop staging path and the fused region path import the same
# functions, keeping all three byte-identical.
from repro.core.nest import (  # noqa: F401 (re-exports)
    device_window_rows,
    window_extent,
    window_rows,
)
from repro.core.timing import timed_pass


# ---------------------------------------------------------------------------
# Slab residency layout (moved here from region.py so the cost model and
# the residency planner share one definition; region re-exports it).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SlabLayout:
    """Chunk-cyclic residency of one buffer between stages.

    Device ``d`` holds stacks of shape ``(local_chunks, chunk, *rest)``;
    (local chunk ``q``, lane ``r``) is global row
    ``base + (q * num_devices + d) * chunk + r``.  ``cover`` rows
    ``[base, base + cover)`` are authoritative; ``has_prior`` marks a
    partial cover whose remaining rows live in a replicated prior copy.
    """

    chunk: int
    num_devices: int
    local_chunks: int
    padded_trip: int
    base: int
    cover: int
    has_prior: bool

    @classmethod
    def of(cls, plan, *, base: int, has_prior: bool) -> "SlabLayout":
        ch = plan.chunks
        return cls(ch.chunk, ch.num_devices, ch.local_chunks,
                   ch.padded_trip, base, plan.loop.trip_count, has_prior)

    def geometry_matches(self, ch) -> bool:
        return (self.chunk == ch.chunk
                and self.num_devices == ch.num_devices
                and self.local_chunks == ch.local_chunks
                and self.padded_trip == ch.padded_trip)


@dataclasses.dataclass(frozen=True)
class AxisSlab:
    """One axis of a rank-2 chunk-cyclic residency layout."""

    chunk: int
    num_devices: int
    local_chunks: int
    padded_trip: int
    base: int
    cover: int

    def geometry_matches(self, ch) -> bool:
        return (self.chunk == ch.chunk
                and self.num_devices == ch.num_devices
                and self.local_chunks == ch.local_chunks
                and self.padded_trip == ch.padded_trip)


@dataclasses.dataclass(frozen=True)
class SlabLayout2:
    """Rank-2 chunk-cyclic residency of one buffer between stages.

    Device ``(d_i, d_j)`` holds stacks ``(n_i, c_i, n_j, c_j, *rest)``;
    (local pair ``(q_i, q_j)``, lanes ``(r_i, r_j)``) is global cell
    ``(bases[0] + (q_i*P_i + d_i)*c_i + r_i,
       bases[1] + (q_j*P_j + d_j)*c_j + r_j)``.  The cover rectangle is
    authoritative; ``has_prior`` marks a partial cover whose remaining
    cells live in a replicated prior copy.
    """

    axes: tuple[AxisSlab, AxisSlab]
    has_prior: bool

    @classmethod
    def of(cls, plan, *, bases: tuple[int, int], has_prior: bool) -> "SlabLayout2":
        axs = tuple(
            AxisSlab(ch.chunk, ch.num_devices, ch.local_chunks,
                     ch.padded_trip, b, t)
            for ch, b, t in zip(plan.chunks_axes, bases, plan.nest.trip_counts))
        return cls(axs, has_prior)

    @property
    def bases(self) -> tuple[int, int]:
        return tuple(a.base for a in self.axes)

    @property
    def covers(self) -> tuple[int, int]:
        return tuple(a.cover for a in self.axes)

    def geometry_matches(self, chunks_axes) -> bool:
        return len(chunks_axes) == 2 and all(
            a.geometry_matches(ch) for a, ch in zip(self.axes, chunks_axes))


# ---------------------------------------------------------------------------
# Cost model
# ---------------------------------------------------------------------------

RESIDENT = "resident"
HALO = "halo"
ALL_GATHER = "all_gather"
REPLICATE = "replicate"

COMM_MODES = ("auto", "gather")

# Per-launch latency term of the aggregated cost model: every collective
# *launch* pays a fixed overhead on top of its wire bytes (dispatch,
# rendezvous, fusion barriers).  Expressed in wire-byte equivalents
# (~1 us at a 50 GB/s link, the ICI constant hlo_analysis.py uses), so
# launch counts and byte counts add in one unit.  The boundary planner
# keeps choosing ops by pure wire bytes (`plan_boundary`); this term is
# what lets the *scheduler* (repro.core.comm_schedule) justify packing k
# same-boundary exchanges into one payload: the bytes are unchanged but
# (k - 1) x alpha of launch overhead disappears.
ALPHA_LAUNCH_BYTES = 4096


def modeled_cost_bytes(wire_bytes: int, launches: int) -> int:
    """Latency-aware cost of a communication plan in byte equivalents:
    ``wire_bytes + ALPHA_LAUNCH_BYTES * launches``."""
    return int(wire_bytes) + ALPHA_LAUNCH_BYTES * int(launches)


@dataclasses.dataclass(frozen=True)
class CommCost:
    """Bytes-on-the-wire model of one boundary lowering.

    ``payload_bytes`` — bytes materialised at each receiving device;
    ``wire_bytes``    — modeled total bytes crossing device links
                        (the quantity the HLO collective counter audits);
    ``hops``          — ring ``ppermute`` shifts emitted (0 for resident
                        and for the collective ops).
    """

    op: str
    payload_bytes: int
    wire_bytes: int
    hops: int = 0


@dataclasses.dataclass(frozen=True)
class BoundaryComm:
    """The planned communication at one stage←buffer boundary."""

    stage: str
    key: str
    op: str
    cost: CommCost
    alternatives: Mapping[str, CommCost]
    reason: str
    # rank-1 halo: (delta_min, delta_max); rank-2: one such pair per axis
    shift: tuple | None = None

    def describe(self) -> str:
        s = (f"{self.stage} <- {self.key!r}: {self.op}"
             f" (payload ~{self.cost.payload_bytes} B/device,"
             f" wire ~{self.cost.wire_bytes} B, hops={self.cost.hops})")
        alts = [f"{op}~{c.wire_bytes} B"
                for op, c in sorted(self.alternatives.items())
                if op != self.op]
        if alts:
            s += " [rejected: " + ", ".join(alts) + "]"
        return s


def row_bytes(aval) -> int:
    """Bytes of one leading-dim row of ``aval``."""
    n = 1
    for s in aval.shape[1:]:
        n *= s
    return int(n) * jnp.dtype(aval.dtype).itemsize


def full_bytes(aval) -> int:
    """Bytes of the whole ``aval`` buffer."""
    n = 1
    for s in aval.shape:
        n *= s
    return int(n) * jnp.dtype(aval.dtype).itemsize


def gather_cost(layout: SlabLayout, aval, *, op: str = ALL_GATHER) -> CommCost:
    """Ring all_gather of the slab stacks, then a local re-slice: every
    device receives the ``(P-1)/P`` of the padded slab it lacks."""
    row = row_bytes(aval)
    p = layout.num_devices
    wire = layout.padded_trip * row * (p - 1)
    return CommCost(op=op, payload_bytes=full_bytes(aval), wire_bytes=wire,
                    hops=0)


def halo_cost(layout: SlabLayout, aval, delta_min: int,
              delta_max: int) -> CommCost:
    """Neighbor ring shifts: each chunk sends ``L`` tail rows left-to-
    right and ``R`` head rows right-to-left (self-sends counted too —
    on one device the gather is free and wins the comparison)."""
    row = row_bytes(aval)
    left = max(0, -delta_min)
    right = max(0, delta_max)
    num_chunks = layout.local_chunks * layout.num_devices
    wire = num_chunks * (left + right) * row
    return CommCost(
        op=HALO,
        payload_bytes=layout.local_chunks * (left + right) * row,
        wire_bytes=wire,
        hops=(1 if left else 0) + (1 if right else 0),
    )


def cell_bytes(aval, lead: int = 2) -> int:
    """Bytes of one cell of ``aval`` (everything past ``lead`` dims)."""
    n = 1
    for s in aval.shape[lead:]:
        n *= s
    return int(n) * jnp.dtype(aval.dtype).itemsize


def gather_cost2(layout: SlabLayout2, aval, *, op: str = ALL_GATHER) -> CommCost:
    """Ring all_gather of a rank-2 slab over both mesh axes, then a local
    re-slice: every device receives the ``(P-1)/P`` of the padded slab it
    lacks (P = the full 2-D mesh)."""
    cell = cell_bytes(aval)
    ax_i, ax_j = layout.axes
    p = ax_i.num_devices * ax_j.num_devices
    wire = ax_i.padded_trip * ax_j.padded_trip * cell * (p - 1)
    return CommCost(op=op, payload_bytes=full_bytes(aval), wire_bytes=wire,
                    hops=0)


def halo_cost2(layout: SlabLayout2, aval, deltas) -> CommCost:
    """Row-ring + column-ring neighbor shifts for a rank-2 window.

    The row pass moves ``L_i + R_i`` lane-rows of ``c_j`` columns per
    chunk pair; the column pass moves ``L_j + R_j`` lane-columns of the
    *extended* ``w_i = c_i + L_i + R_i`` rows — the corner cells ride
    the second pass (two hops, no diagonal sends).  Self-sends counted
    too, exactly as in the 1-D model.
    """
    cell = cell_bytes(aval)
    ax_i, ax_j = layout.axes
    (dmin_i, dmax_i), (dmin_j, dmax_j) = deltas
    li, ri = max(0, -dmin_i), max(0, dmax_i)
    lj, rj = max(0, -dmin_j), max(0, dmax_j)
    k_i = ax_i.local_chunks * ax_i.num_devices
    k_j = ax_j.local_chunks * ax_j.num_devices
    w_i = ax_i.chunk + li + ri
    per_pair = (li + ri) * ax_j.chunk + w_i * (lj + rj)
    wire = k_i * k_j * per_pair * cell
    return CommCost(
        op=HALO,
        payload_bytes=ax_i.local_chunks * ax_j.local_chunks * per_pair * cell,
        wire_bytes=wire,
        hops=sum(1 for v in (li, ri, lj, rj) if v),
    )


@timed_pass("plan_comm")
def plan_boundary2(
    *,
    stage: str,
    key: str,
    layout: SlabLayout2,
    chunks_axes,
    trips,
    aval,
    in_strategy: str,
    halo_axes,
    shard_ndim: int,
    needs_replicated: bool,
    mode: str = "auto",
) -> BoundaryComm:
    """Rank-2 :func:`plan_boundary`: pick the cheapest feasible lowering
    for one 2-D slab→consumer boundary (resident / row+column halo rings
    / all_gather / replicate), by the same bytes-on-the-wire model."""
    if mode not in COMM_MODES:
        raise ValueError(f"unknown comm mode {mode!r}; expected {COMM_MODES}")
    g_op = REPLICATE if needs_replicated else ALL_GATHER
    g_cost = gather_cost2(layout, aval, op=g_op)
    alternatives: dict[str, CommCost] = {g_op: g_cost}

    if needs_replicated or in_strategy != "shard_halo":
        return BoundaryComm(
            stage=stage, key=key, op=REPLICATE,
            cost=dataclasses.replace(g_cost, op=REPLICATE),
            alternatives=alternatives,
            reason="consumer needs the full buffer on every rank",
        )
    if shard_ndim != 2:
        return BoundaryComm(
            stage=stage, key=key, op=ALL_GATHER, cost=g_cost,
            alternatives=alternatives,
            reason="consumer shards only the leading axis of a 2-D slab",
        )
    if chunks_axes is None or not layout.geometry_matches(chunks_axes):
        return BoundaryComm(
            stage=stage, key=key, op=ALL_GATHER, cost=g_cost,
            alternatives=alternatives,
            reason="chunk geometry differs between producer and consumer",
        )

    halos = halo_axes if halo_axes is not None else ((0, 0), (0, 0))
    deltas = tuple(
        (h[0] - a.base, h[1] - a.base)
        for h, a in zip(halos, layout.axes))

    if all(d == (0, 0) for d in deltas) \
            and layout.covers == tuple(trips):
        cost = CommCost(op=RESIDENT, payload_bytes=0, wire_bytes=0, hops=0)
        alternatives[RESIDENT] = cost
        return BoundaryComm(
            stage=stage, key=key, op=RESIDENT, cost=cost,
            alternatives=alternatives,
            reason="producer OUT layout equals consumer IN layout",
        )

    feasible = True
    why = ""
    for d, ((dmin, dmax), ax, h, t) in enumerate(
            zip(deltas, layout.axes, halos, trips)):
        left, right = max(0, -dmin), max(0, dmax)
        if left > ax.chunk or right > ax.chunk:
            feasible = False
            why = (f"axis-{d} halo wider than one chunk "
                   "(multi-hop exchange not emitted)")
            break
        if h[0] < ax.base and not layout.has_prior:
            feasible = False
            why = (f"axis-{d} window reads below the slab and no prior "
                   "copy exists")
            break
        if t + h[1] > ax.base + ax.cover and not layout.has_prior:
            feasible = False
            why = (f"axis-{d} window reads beyond the slab cover and no "
                   "prior copy exists")
            break

    if feasible:
        h_cost = halo_cost2(layout, aval, deltas)
        alternatives[HALO] = h_cost
        if mode == "auto" and h_cost.wire_bytes < g_cost.wire_bytes:
            return BoundaryComm(
                stage=stage, key=key, op=HALO, cost=h_cost,
                alternatives=alternatives,
                reason=(f"row+column neighbor shifts move "
                        f"{h_cost.wire_bytes} B vs {g_cost.wire_bytes} B "
                        "for the gather"),
                shift=deltas,
            )
        why = ("comm mode 'gather' pins the PR 1 baseline" if mode != "auto"
               else f"gather is no more expensive "
                    f"({g_cost.wire_bytes} B <= {h_cost.wire_bytes} B)")

    return BoundaryComm(
        stage=stage, key=key, op=ALL_GATHER, cost=g_cost,
        alternatives=alternatives, reason=why,
    )


@timed_pass("plan_comm")
def plan_boundary(
    *,
    stage: str,
    key: str,
    layout: SlabLayout,
    chunks,
    trip: int,
    aval,
    in_strategy: str,
    halo: tuple[int, int] | None,
    needs_replicated: bool,
    mode: str = "auto",
) -> BoundaryComm:
    """Pick the cheapest feasible lowering for one slab→consumer boundary.

    ``needs_replicated`` marks consumers that must see the full buffer
    (whole-array reads, out-merge priors): the gather is then forced and
    reported as ``replicate``.  ``mode="gather"`` disables the halo
    strategy — the PR 1 baseline, kept for measurement.
    """
    if mode not in COMM_MODES:
        raise ValueError(f"unknown comm mode {mode!r}; expected {COMM_MODES}")
    g_op = REPLICATE if needs_replicated else ALL_GATHER
    g_cost = gather_cost(layout, aval, op=g_op)
    alternatives: dict[str, CommCost] = {g_op: g_cost}

    if needs_replicated or in_strategy not in ("shard", "shard_halo"):
        return BoundaryComm(
            stage=stage, key=key, op=REPLICATE,
            cost=dataclasses.replace(g_cost, op=REPLICATE),
            alternatives=alternatives,
            reason="consumer needs the full buffer on every rank",
        )

    b_min, b_max = halo if halo is not None else (0, 0)
    if chunks is None or not layout.geometry_matches(chunks):
        return BoundaryComm(
            stage=stage, key=key, op=ALL_GATHER, cost=g_cost,
            alternatives=alternatives,
            reason="chunk geometry differs between producer and consumer",
        )

    delta_min = b_min - layout.base
    delta_max = b_max - layout.base

    if delta_min == 0 and delta_max == 0 and layout.cover == trip:
        cost = CommCost(op=RESIDENT, payload_bytes=0, wire_bytes=0, hops=0)
        alternatives[RESIDENT] = cost
        return BoundaryComm(
            stage=stage, key=key, op=RESIDENT, cost=cost,
            alternatives=alternatives,
            reason="producer OUT layout equals consumer IN layout",
        )

    # Halo feasibility: one-hop shifts, and any window rows falling
    # outside the slab's cover must be servable from a replicated prior.
    left = max(0, -delta_min)
    right = max(0, delta_max)
    feasible = left <= layout.chunk and right <= layout.chunk
    why = "halo wider than one chunk (multi-hop exchange not emitted)"
    if feasible and b_min < layout.base and not layout.has_prior:
        feasible = False
        why = "window reads below the slab and no prior copy exists"
    if (feasible and trip + b_max > layout.base + layout.cover
            and not layout.has_prior):
        feasible = False
        why = "window reads beyond the slab cover and no prior copy exists"

    if feasible:
        h_cost = halo_cost(layout, aval, delta_min, delta_max)
        alternatives[HALO] = h_cost
        if mode == "auto" and h_cost.wire_bytes < g_cost.wire_bytes:
            return BoundaryComm(
                stage=stage, key=key, op=HALO, cost=h_cost,
                alternatives=alternatives,
                reason=(f"neighbor shifts move {h_cost.wire_bytes} B vs "
                        f"{g_cost.wire_bytes} B for the gather"),
                shift=(delta_min, delta_max),
            )
        why = ("comm mode 'gather' pins the PR 1 baseline" if mode != "auto"
               else f"gather is no more expensive "
                    f"({g_cost.wire_bytes} B <= {h_cost.wire_bytes} B)")

    return BoundaryComm(
        stage=stage, key=key, op=ALL_GATHER, cost=g_cost,
        alternatives=alternatives, reason=why,
    )


# ---------------------------------------------------------------------------
# The halo emitter (runs inside the fused shard_map)
# ---------------------------------------------------------------------------


def _ring_extend(stacks, *, axis: str, num_devices: int, device_index,
                 chunk: int, delta_min: int, delta_max: int,
                 stack_dim: int = 0, lane_dim: int = 1):
    """Widen one chunk-cyclic axis of a resident slab into read windows
    via neighbor ring shifts: dims ``(stack_dim, lane_dim)`` go from
    ``(n_loc, chunk)`` to ``(n_loc, chunk + extent)``.

    Chunk adjacency under the cyclic assignment: chunk ``j+1`` lives on
    device ``d+1`` at the same local index — except on the last device,
    where it wraps to device 0's *next* local index; symmetrically for
    chunk ``j-1``.  Window row ``r`` of local chunk ``q`` holds slab row
    ``j*chunk + delta_min + r`` (rows outside the producing slab are the
    caller's to patch).
    """
    p, c = num_devices, chunk
    left = max(0, -delta_min)
    right = max(0, delta_max)
    if left > c or right > c:
        raise ValueError(
            f"halo shift ({delta_min}, {delta_max}) exceeds one chunk "
            f"(chunk={c}); the planner should have chosen a gather")
    x = jnp.moveaxis(stacks, (stack_dim, lane_dim), (0, 1))
    parts = []
    if left:
        tails = x[:, c - left:]
        recv = jax.lax.ppermute(
            tails, axis, perm=[((i - 1) % p, i) for i in range(p)])
        # device 0's chunk j-1 is the last device's PREVIOUS local chunk
        rolled = jnp.concatenate([recv[:1], recv[:-1]], axis=0)
        parts.append(jnp.where(device_index == 0, rolled, recv))
    parts.append(x[:, max(0, delta_min):c + min(0, delta_max)])
    if right:
        heads = x[:, :right]
        recv = jax.lax.ppermute(
            heads, axis, perm=[((i + 1) % p, i) for i in range(p)])
        # the last device's chunk j+1 is device 0's NEXT local chunk
        rolled = jnp.concatenate([recv[1:], recv[-1:]], axis=0)
        parts.append(jnp.where(device_index == p - 1, rolled, recv))
    win = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
    return jnp.moveaxis(win, (0, 1), (stack_dim, lane_dim))


def halo_exchange(
    stacks,
    *,
    axis: str,
    num_devices: int,
    device_index,
    chunk: int,
    delta_min: int,
    delta_max: int,
    prior=None,
    base: int = 0,
    cover: int | None = None,
    dtype=None,
):
    """Build each local chunk's read window from a resident slab via
    neighbor ring shifts.

    ``stacks`` is this device's produced slab ``(n_loc, chunk, *rest)``
    where (local chunk ``q``, lane ``r``) is slab row
    ``(q * num_devices + device_index) * chunk + r``.  Returns
    ``(n_loc, width, *rest)`` windows whose row ``r`` holds slab row
    ``j*chunk + delta_min + r`` — exactly the layout
    :func:`device_window_rows` produces from a replicated copy, so the
    consumer's ``nest.ShiftedWindow`` indexing is identical on both paths.

    Chunk adjacency under the cyclic assignment: chunk ``j+1`` lives on
    device ``d+1`` at the same local index — except on the last device,
    where it wraps to device 0's *next* local index; symmetrically for
    chunk ``j-1``.  Rows outside the slab's ``[0, cover)`` are patched
    from the replicated ``prior`` copy (the boundary rows a partial
    write never touched); remaining out-of-range rows are only consumed
    by masked padding lanes.
    """
    win = _ring_extend(
        stacks, axis=axis, num_devices=num_devices,
        device_index=device_index, chunk=chunk, delta_min=delta_min,
        delta_max=delta_max)
    return patch_window_prior(
        win, num_devices=num_devices, device_index=device_index,
        chunk=chunk, delta_min=delta_min, prior=prior, base=base,
        cover=cover, dtype=dtype)


def patch_window_prior(
    win,
    *,
    num_devices: int,
    device_index,
    chunk: int,
    delta_min: int,
    prior=None,
    base: int = 0,
    cover: int | None = None,
    dtype=None,
):
    """Patch window rows outside the slab's ``[0, cover)`` from the
    replicated ``prior`` copy and cast to the consumer dtype — the
    non-communicating half of :func:`halo_exchange`, shared with the
    aggregated packing emitters (:mod:`repro.core.comm_schedule`)."""
    p, c = num_devices, chunk
    if prior is not None:
        n_loc, width = win.shape[0], win.shape[1]
        rho = _window_positions(n_loc, width, p, c, device_index, delta_min)
        pos = jnp.clip(base + rho, 0, prior.shape[0] - 1)
        pvals = jnp.take(prior, pos, axis=0)
        cov = cover if cover is not None else n_loc * p * c
        inside = (rho >= 0) & (rho < cov)
        mask = inside.reshape(inside.shape + (1,) * (win.ndim - 2))
        win = jnp.where(mask, win, pvals.astype(win.dtype))
    if dtype is not None:
        win = win.astype(dtype)
    return win


def _window_positions(n_loc, width, p, c, device_index, delta_min):
    """k-space positions ``(n_loc, width)`` of this device's windows
    relative to the producing slab's base."""
    j0 = (jnp.arange(n_loc, dtype=jnp.int32)[:, None] * p
          + device_index) * c
    return j0 + delta_min + jnp.arange(width, dtype=jnp.int32)[None, :]


def halo_exchange2(
    stacks,
    *,
    axes: tuple[str, str],
    num_devices: tuple[int, int],
    device_indices,
    chunks: tuple[int, int],
    deltas,
    prior=None,
    bases: tuple[int, int] = (0, 0),
    covers: tuple[int, int] | None = None,
    dtype=None,
):
    """Rank-2 halo exchange: build each local (chunk_i, chunk_j) pair's
    2-D read window from a resident slab via row-ring then column-ring
    shifts.

    ``stacks`` is this device's produced slab ``(n_i, c_i, n_j, c_j,
    *rest)``; returns ``(n_i, w_i, n_j, w_j, *rest)`` windows.  The row
    pass widens axis 0 along the ``axes[0]`` rings; the column pass then
    widens axis 1 of the *already-extended* windows along the ``axes[1]``
    rings — so the corner blocks travel two hops (the standard 2-D halo
    corner treatment: no diagonal sends needed).  Positions outside the
    slab's cover rectangle are patched from the replicated ``prior``
    copy (the boundary rows/columns a partial write never touched).
    """
    (p_i, p_j) = num_devices
    (c_i, c_j) = chunks
    (d_i, d_j) = device_indices
    (dmin_i, dmax_i), (dmin_j, dmax_j) = deltas
    win = _ring_extend(
        stacks, axis=axes[0], num_devices=p_i, device_index=d_i,
        chunk=c_i, delta_min=dmin_i, delta_max=dmax_i,
        stack_dim=0, lane_dim=1)
    win = _ring_extend(
        win, axis=axes[1], num_devices=p_j, device_index=d_j,
        chunk=c_j, delta_min=dmin_j, delta_max=dmax_j,
        stack_dim=2, lane_dim=3)
    return patch_window_prior2(
        win, num_devices=num_devices, device_indices=device_indices,
        chunks=chunks, deltas=deltas, prior=prior, bases=bases,
        covers=covers, dtype=dtype)


def patch_window_prior2(
    win,
    *,
    num_devices: tuple[int, int],
    device_indices,
    chunks: tuple[int, int],
    deltas,
    prior=None,
    bases: tuple[int, int] = (0, 0),
    covers: tuple[int, int] | None = None,
    dtype=None,
):
    """Rank-2 :func:`patch_window_prior`: patch positions outside the
    slab's cover rectangle from the replicated ``prior`` copy."""
    (p_i, p_j) = num_devices
    (c_i, c_j) = chunks
    (d_i, d_j) = device_indices
    (dmin_i, _), (dmin_j, _) = deltas
    if prior is not None:
        n_i, w_i, n_j, w_j = win.shape[:4]
        rho_i = _window_positions(n_i, w_i, p_i, c_i, d_i, dmin_i)
        rho_j = _window_positions(n_j, w_j, p_j, c_j, d_j, dmin_j)
        pos_i = jnp.clip(bases[0] + rho_i, 0, prior.shape[0] - 1)
        pos_j = jnp.clip(bases[1] + rho_j, 0, prior.shape[1] - 1)
        pvals = jnp.take(prior, pos_i, axis=0)        # (n_i, w_i, N1, *)
        pvals = jnp.take(pvals, pos_j, axis=2)        # (n_i, w_i, n_j, w_j, *)
        cov_i = covers[0] if covers is not None else n_i * p_i * c_i
        cov_j = covers[1] if covers is not None else n_j * p_j * c_j
        inside = ((rho_i >= 0) & (rho_i < cov_i))[:, :, None, None] \
            & ((rho_j >= 0) & (rho_j < cov_j))[None, None, :, :]
        mask = inside.reshape(inside.shape + (1,) * (win.ndim - 4))
        win = jnp.where(mask, win, pvals.astype(win.dtype))
    if dtype is not None:
        win = win.astype(dtype)
    return win


# ---------------------------------------------------------------------------
# Facade
# ---------------------------------------------------------------------------


def plan_comm(
    region,
    env: Mapping[str, Any],
    num_devices: int | tuple,
    *,
    axis: str | tuple | None = None,
    comm: str = "auto",
) -> list[BoundaryComm]:
    """Plan every inter-loop boundary of a region: the cost-modeled
    communication schedule, one :class:`BoundaryComm` per slab handoff.

    Accepts a :class:`~repro.core.pragma.ParallelRegion` (or a single
    :class:`~repro.core.pragma.ParallelFor`, wrapped) plus example/aval
    inputs; returns the decisions in stage order.  Rank-2 regions take
    per-axis device counts, e.g. ``num_devices=(4, 2)``.  This is the
    planning half of :func:`repro.core.region.region_to_mpi` — the same
    decisions that lowering executes.
    """
    from repro.core import pragma
    from repro.core.region import plan_region

    if isinstance(region, pragma.ParallelFor):
        region = pragma.ParallelRegion((region,))
    if region.rank == 2:
        if axis is None:
            axis = ("i", "j")
        if not isinstance(num_devices, tuple):
            raise ValueError(
                "collapse=2 regions need per-axis device counts, "
                f"e.g. num_devices=(4, 2); got {num_devices!r}")
    elif axis is None:
        axis = "data"
    rp = plan_region(region, env, num_devices, axis=axis, comm=comm)
    return rp.comms
