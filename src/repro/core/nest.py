"""Loop-nest IR — the single owner of iteration-space geometry.

The paper's pipeline (§3.1.2–3.1.4) reasons about ONE canonical loop; its
benchmark suite (matrix multiply, Jacobi stencils) is dominated by 2-D
kernels expressed as ``collapse(2)`` nests.  This module introduces the
:class:`LoopNest` IR that every lowering layer consumes:

* **axes** — one canonicalised :class:`~repro.core.loop.LoopInfo` per
  induction variable (rank 1 or 2), each with its own schedule-derived
  :class:`~repro.core.schedule.ChunkPlan`;
* **affine access maps** — :class:`NestAffine` tracks indices affine in
  *several* iterators (``a_i*i + a_j*j + b``), the rank-general
  analogue of :class:`repro.core.context.Affine`;
* **window geometry** — where chunk ``j``'s read window lives in the
  buffer (``window_rows`` / ``device_window_rows`` / ``window_extent``),
  shared by the per-loop staging path, the fused region path and the
  communication cost model so all three build byte-identical slabs;
* **slab slicing** — the chunk-cyclic pad/reshape staging
  (:func:`pad_reshape`, :func:`halo_slabs`, :func:`halo_slabs2`,
  :func:`unpad_flat`) and the in-shard_map local slicing
  (:func:`local_slabs`, :func:`local_slabs2`);
* **env substitution** — :class:`ShiftedWindow` serves ``x[i]`` /
  ``x[i, j]``-style body reads from a local slab with per-axis offsets.

Before this module the 1-D versions of these helpers were duplicated
three ways (``transform._halo_slabs`` / ``region._local_slabs`` /
``comm`` window geometry); they now live here alone and
:mod:`repro.core.transform`, :mod:`repro.core.region` and
:mod:`repro.core.comm` all import them.

Chunk-cyclic layout (per axis): iteration ``k`` lives in chunk
``k // c``; chunk ``j`` executes on device ``j % P`` as local chunk
``j // P``; the padded axis reshapes to ``(n_loc, P, c)`` whose middle
dim IS the device axis.  A rank-2 nest composes two such layouts: the
buffer reshapes to ``(n_i, P_i, c_i, n_j, P_j, c_j, *rest)`` over a 2-D
``(i, j)`` mesh.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.loop import LoopInfo, LoopNotCanonical, analyze_loop


# ---------------------------------------------------------------------------
# The nest IR
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LoopNest:
    """A rank-1 or rank-2 canonical loop nest.

    Axis ``d`` iterates ``i_d = start_d + k_d * step_d`` for
    ``k_d in [0, trip_d)``; the iteration space is the cross product
    (the ``collapse(2)`` semantics: one flat parallel region over
    ``trip_0 * trip_1`` iterations).
    """

    axes: tuple[LoopInfo, ...]

    def __post_init__(self) -> None:
        if not 1 <= len(self.axes) <= 2:
            raise LoopNotCanonical(
                f"loop nests of rank {len(self.axes)} are not supported "
                "(collapse(2) is the maximum)")

    @property
    def rank(self) -> int:
        return len(self.axes)

    @property
    def trip_counts(self) -> tuple[int, ...]:
        return tuple(ax.trip_count for ax in self.axes)

    @property
    def total_trip(self) -> int:
        n = 1
        for ax in self.axes:
            n *= ax.trip_count
        return n

    @classmethod
    def from_program(cls, program) -> "LoopNest":
        """Build the nest from a :class:`~repro.core.pragma.ParallelFor`
        (the Loop Analysis stage, per axis)."""
        return cls(tuple(analyze_loop(s, e, t) for s, e, t in program.bounds))


@dataclasses.dataclass(frozen=True)
class NestAffine:
    """Index affine in the nest iterators: ``sum_d coeffs[d]*i_d + b``."""

    coeffs: tuple[int, ...]
    b: int

    def __add__(self, other: "NestAffine") -> "NestAffine":
        return NestAffine(
            tuple(a + o for a, o in zip(self.coeffs, other.coeffs)),
            self.b + other.b)

    def __sub__(self, other: "NestAffine") -> "NestAffine":
        return NestAffine(
            tuple(a - o for a, o in zip(self.coeffs, other.coeffs)),
            self.b - other.b)

    def scale(self, k: int) -> "NestAffine":
        return NestAffine(tuple(a * k for a in self.coeffs), self.b * k)

    @property
    def is_const(self) -> bool:
        return all(a == 0 for a in self.coeffs)

    def k_space(self, nest: LoopNest) -> "NestAffine":
        """Rebase from iterator space to iteration-number space:
        ``i_d = start_d + k_d*step_d`` substituted per axis."""
        coeffs = tuple(a * ax.step for a, ax in zip(self.coeffs, nest.axes))
        b = self.b + sum(a * ax.start
                         for a, ax in zip(self.coeffs, nest.axes))
        return NestAffine(coeffs, b)

    def unit_axis(self) -> int | None:
        """The single nest axis this map follows with coefficient 1
        (``k_d + b``), or None if it is not such a unit map."""
        hits = [d for d, a in enumerate(self.coeffs) if a != 0]
        if len(hits) == 1 and self.coeffs[hits[0]] == 1:
            return hits[0]
        return None

    def __repr__(self) -> str:
        names = ("i", "j", "k")
        terms = [("" if a == 1 else f"{a}*") + names[d]
                 for d, a in enumerate(self.coeffs) if a != 0]
        if not terms:
            return str(self.b)
        s = "+".join(terms)
        return s if self.b == 0 else f"{s}{self.b:+d}"


# ---------------------------------------------------------------------------
# Window geometry (single source of truth; comm re-exports these so the
# cost model, the staging path and the fused path stay byte-identical)
# ---------------------------------------------------------------------------


def window_extent(chunk: int, halo: tuple[int, int]) -> int:
    """Width of one chunk's read window: ``chunk + (b_max - b_min)``."""
    b_min, b_max = halo
    return chunk + (b_max - b_min)


def slot_chunk_ids(ch) -> np.ndarray:
    """Global chunk id at each slot position.  Identity for the cyclic
    deal; the plan's ``slot_map`` permutation for straggler-weighted
    schedules (sentinel slots point at a padding chunk whose iterations
    are all masked)."""
    if ch.slot_map is not None:
        return np.asarray(ch.slot_map, dtype=np.int64)
    return np.arange(ch.num_chunks, dtype=np.int64)


def restore_chunk_order(ch) -> np.ndarray | None:
    """Slot index of every *real* chunk, in global chunk order — the
    inverse of ``slot_map`` used to reassemble outputs.  ``None`` for
    the cyclic deal (a plain reshape already restores order)."""
    if ch.slot_map is None:
        return None
    inv = np.empty(ch.real_chunks, dtype=np.int64)
    for s, j in enumerate(ch.slot_map):
        if j < ch.real_chunks:
            inv[j] = s
    return inv


def window_rows(ch, halo: tuple[int, int], nrows: int) -> np.ndarray:
    """Static (jit-level) row indices of every chunk's read window:
    ``(num_chunks, width)``, clipped in-bounds (out-of-range rows are
    only ever consumed by masked padding lanes).  Rows come out in
    *slot* order so the trailing ``(n_loc, P, ...)`` reshape always
    places a device's slabs on the device axis, weighted or not."""
    b_min, _ = halo
    width = window_extent(ch.chunk, halo)
    rows = (slot_chunk_ids(ch)[:, None] * ch.chunk + b_min
            + np.arange(width)[None, :])
    return np.clip(rows, 0, max(0, nrows - 1))


def device_window_rows(ch, halo: tuple[int, int], device_index,
                       nrows: int):
    """Traced (in-shard_map) row indices of THIS device's chunk windows:
    ``(local_chunks, width)`` — the fused analogue of
    :func:`window_rows` for slicing a replicated buffer locally."""
    b_min, _ = halo
    width = window_extent(ch.chunk, halo)
    base = (jnp.arange(ch.local_chunks, dtype=jnp.int32)[:, None]
            * ch.num_devices + device_index) * ch.chunk
    rows = base + b_min + jnp.arange(width, dtype=jnp.int32)[None, :]
    return jnp.clip(rows, 0, max(0, nrows - 1))


# ---------------------------------------------------------------------------
# Slab slicing — jit-level staging (chunk-cyclic pad/reshape)
# ---------------------------------------------------------------------------


def pad_reshape(x, ch):
    """(T, *rest) -> (n_loc, P, c, *rest) chunk-cyclic (or, with a
    weighted plan, slot-ordered) layout."""
    pad = ch.padded_trip - x.shape[0]
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
    if ch.slot_map is not None:
        chunks = x.reshape((ch.num_chunks, ch.chunk) + x.shape[1:])
        x = chunks[slot_chunk_ids(ch)].reshape(
            (ch.padded_trip,) + x.shape[1:])
    return x.reshape((ch.local_chunks, ch.num_devices, ch.chunk) + x.shape[1:])


def halo_slabs(x, ch, halo: tuple[int, int]):
    """(N, *rest) -> (n_loc, P, c + halo_width, *rest): each chunk's slab
    carries its read window ``[j*c + b_min, (j+1)*c - 1 + b_max]`` — the
    stencil halo exchange (rows duplicated at chunk edges)."""
    width = window_extent(ch.chunk, halo)
    rows = window_rows(ch, halo, x.shape[0])
    slab = x[rows]                                   # (K', width, *rest)
    return slab.reshape((ch.local_chunks, ch.num_devices, width)
                        + x.shape[1:])


def halo_slabs2(x, chs, halos):
    """(N0, N1, *rest) -> (n_i, P_i, w_i, n_j, P_j, w_j, *rest): the
    rank-2 staging — each (chunk_i, chunk_j) pair's slab carries its 2-D
    read window (per-axis halo rows/columns duplicated at chunk edges)."""
    ch_i, ch_j = chs
    halo_i, halo_j = halos
    rows_i = window_rows(ch_i, halo_i, x.shape[0])   # (K_i, w_i)
    rows_j = window_rows(ch_j, halo_j, x.shape[1])   # (K_j, w_j)
    slab = x[rows_i[:, :, None, None], rows_j[None, None, :, :]]
    return slab.reshape(
        (ch_i.local_chunks, ch_i.num_devices, rows_i.shape[1],
         ch_j.local_chunks, ch_j.num_devices, rows_j.shape[1])
        + x.shape[2:])


def unpad_flat(slabs, ch, t: int):
    """(n_loc, P, c, *rest) -> (T, *rest).  With a weighted plan the
    slabs sit in slot order; the inverse slot gather puts the real
    chunks back in global order before the flatten."""
    inv = restore_chunk_order(ch)
    if inv is None:
        flat = slabs.reshape((ch.padded_trip,) + slabs.shape[3:])
        return flat[:t]
    chunks = slabs.reshape((ch.num_chunks, ch.chunk) + slabs.shape[3:])
    flat = chunks[inv].reshape((len(inv) * ch.chunk,) + slabs.shape[3:])
    return flat[:t]


def unpad_flat2(slabs, chs, trips):
    """(n_i, P_i, c_i, n_j, P_j, c_j, *rest) -> (T_i, T_j, *rest)."""
    ch_i, ch_j = chs
    t_i, t_j = trips
    inv_i = restore_chunk_order(ch_i)
    inv_j = restore_chunk_order(ch_j)
    if inv_i is None and inv_j is None:
        flat = slabs.reshape((ch_i.padded_trip, ch_j.padded_trip)
                             + slabs.shape[6:])
        return flat[:t_i, :t_j]
    idx_i = inv_i if inv_i is not None else np.arange(ch_i.num_chunks)
    idx_j = inv_j if inv_j is not None else np.arange(ch_j.num_chunks)
    chunks = slabs.reshape(
        (ch_i.num_chunks, ch_i.chunk, ch_j.num_chunks, ch_j.chunk)
        + slabs.shape[6:])
    chunks = jnp.take(jnp.take(chunks, idx_i, axis=0), idx_j, axis=2)
    flat = chunks.reshape((len(idx_i) * ch_i.chunk,
                           len(idx_j) * ch_j.chunk) + slabs.shape[6:])
    return flat[:t_i, :t_j]


# ---------------------------------------------------------------------------
# Slab slicing — in-shard_map local windows (pure local indexing of a
# replicated buffer; the fused analogue of the staging above)
# ---------------------------------------------------------------------------


def local_slabs(x, ch, halo: tuple[int, int], device_index):
    """Slice THIS device's chunk slabs out of a replicated buffer:
    ``(n_loc, width, *rest)`` — same window geometry as
    :func:`halo_slabs`, computed per device inside the shard_map."""
    rows = device_window_rows(ch, halo, device_index, x.shape[0])
    return jnp.take(x, rows, axis=0)


def local_slabs2(x, chs, halos, device_indices):
    """Rank-2 :func:`local_slabs`: ``(n_i, w_i, n_j, w_j, *rest)``."""
    ch_i, ch_j = chs
    halo_i, halo_j = halos
    d_i, d_j = device_indices
    rows_i = device_window_rows(ch_i, halo_i, d_i, x.shape[0])
    rows_j = device_window_rows(ch_j, halo_j, d_j, x.shape[1])
    out = jnp.take(x, rows_i, axis=0)                # (n_i, w_i, N1, *rest)
    return jnp.take(out, rows_j, axis=2)             # (n_i, w_i, n_j, w_j, *)


# ---------------------------------------------------------------------------
# Tile derivation — Pallas lowering geometry (consumed by
# repro.core.pallas_lower; lives here because the chunk-cyclic layout
# above is the single owner of iteration-space geometry)
# ---------------------------------------------------------------------------


# Minimum second-to-minor tile extent per element width (the TPU packing
# rule: 8 sublanes of 32-bit lanes, narrower dtypes pack 2x/4x deeper).
_SUBLANE_BY_ITEMSIZE = {8: 8, 4: 8, 2: 16, 1: 32}

# Lanes per kernel tile are capped so one tile's values stay comfortably
# inside VMEM whatever the chunk size.  A multiple of 128: a chunk split
# into several tiles then has lane-aligned tiles, as Mosaic requires of
# a block's minor dimension (one tile per chunk spans the axis instead).
MAX_TILE_LANES = 256


def sublane_for(dtype) -> int:
    """Minimum tile granularity (second-to-minor extent) for ``dtype``."""
    return _SUBLANE_BY_ITEMSIZE.get(np.dtype(dtype).itemsize, 8)


@dataclasses.dataclass(frozen=True)
class AxisTiles:
    """Tiling of one axis's chunk lanes for the Pallas backend.

    A chunk's ``chunk`` lanes are covered by ``n_tiles`` tiles of
    ``tile`` lanes each; the last tile's ``masked_lanes`` trailing lanes
    are padding (their iteration numbers clamp to the final in-bounds
    iteration and the produced garbage is sliced off after the kernel,
    exactly like the chunk-cyclic trip padding).
    """

    chunk: int
    tile: int
    n_tiles: int
    padded: int

    @property
    def masked_lanes(self) -> int:
        return self.padded - self.chunk

    def cover(self) -> list[tuple[int, int]]:
        """``(start_lane, valid_lanes)`` per tile — a partition of
        ``[0, chunk)`` with no overlap and no gap."""
        return [(ti * self.tile, min(self.tile, self.chunk - ti * self.tile))
                for ti in range(self.n_tiles)]


def derive_axis_tiles(chunk: int, dtype,
                      max_tile: int = MAX_TILE_LANES) -> AxisTiles:
    """Tile one axis's chunk: ``tile`` is the chunk rounded up to the
    dtype's sublane multiple, capped at ``max_tile``; remainder lanes of
    the last tile are masked."""
    sub = sublane_for(dtype)
    tile = min(max(int(chunk), 1), int(max_tile))
    tile = -(-tile // sub) * sub
    n_tiles = max(1, -(-int(chunk) // tile))
    return AxisTiles(chunk=int(chunk), tile=tile, n_tiles=n_tiles,
                     padded=n_tiles * tile)


# ---------------------------------------------------------------------------
# Env substitution: sliced-read service from the local slab
# ---------------------------------------------------------------------------


class SubstitutionFailed(Exception):
    pass


class ShiftedWindow:
    """Stands in for a shared buffer whose accesses are ``x[i]`` /
    ``x[i, j]``-style unit-stride reads on the leading axes; serves them
    from the local chunk window instead.

    ``offsets[d]`` is the global position held by window row 0 of axis
    ``d``: reading ``x[a, b]`` returns
    ``window[a - offsets[0], b - offsets[1]]``.  Axes beyond
    ``len(offsets)`` pass through untouched (whole-axis slices).
    """

    def __init__(self, window, offsets: tuple, virtual_shape, dtype):
        self._win = window
        self._offsets = tuple(offsets)
        self.shape = tuple(virtual_shape)
        self.dtype = dtype
        self.ndim = len(self.shape)

    def __getitem__(self, idx):
        idx = idx if isinstance(idx, tuple) else (idx,)
        r = len(self._offsets)
        if len(idx) < r:
            raise SubstitutionFailed(
                f"sliced-read substitution needs {r} leading indices, "
                f"got {len(idx)}")
        out = self._win
        for d, (ix, off) in enumerate(zip(idx[:r], self._offsets)):
            out = jax.lax.dynamic_index_in_dim(
                out, jnp.asarray(ix - off, jnp.int32), 0, keepdims=False)
        rest = tuple(idx[r:])
        return out[rest] if rest else out

    def __len__(self):
        return self.shape[0]

    def _no(self, *a, **k):  # pragma: no cover - guard path
        raise SubstitutionFailed(
            "sliced-read substitution saw a non-getitem use; this buffer "
            "should have been classified as a whole-array read"
        )

    __add__ = __radd__ = __mul__ = __rmul__ = __sub__ = __rsub__ = _no
    __truediv__ = __rtruediv__ = __matmul__ = __rmatmul__ = _no
    __neg__ = __pow__ = __array__ = _no
