"""Tile evaluation of a loop body, shared by the lax and Pallas lowerings.

The body is traced once on scalar iterators (as Context Analysis traces
it) and its jaxpr is evaluated over a whole tile of lanes: every
equation runs batched over the lanes through ``jax.vmap``, except the
reads ``x[i+b]`` / ``x[i+b, j+c]`` of a chunk window, which become static
slices of the window.  A vmapped body would turn those reads into
gathers: Mosaic cannot lower them, and XLA runs them far below the HBM
roof.

Two lowerings evaluate bodies this way:

* :mod:`repro.core.pallas_lower` — one tile of one chunk per kernel
  program (:func:`eval_body` inside the kernel);
* :mod:`repro.core.transform` — a stage's whole local chunk stack at
  once (:func:`eval_local_chunks`), mapped over the stack axis of each
  loop axis so every window read stays a slice.

Both hand the dense per-lane values to :func:`merge_chunk_values`,
which rebuilds the ``(carry, ys)`` contract of the chunk scans in
:mod:`repro.core.transform`.  A body whose window reads are not
unit-stride reads inside the window raises
:class:`~repro.core.nest.SubstitutionFailed`.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jcore

from repro.core import context as ctx_mod
from repro.core import nest as nest_mod
from repro.core import reduction as red_mod
from repro.core.nest import NestAffine


@dataclasses.dataclass(frozen=True)
class Body:
    closed: Any                # ClosedJaxpr of body(i[, j], env)
    env_keys: tuple            # env keys in invar order
    value_pos: dict            # written key -> flat position of its value


def trace_body(plan, program) -> Body:
    infos = plan.context.vars
    keys = tuple(sorted(plan.context.env_keys))
    env = {k: jax.ShapeDtypeStruct(infos[k].shape, infos[k].dtype)
           for k in keys}
    it = jax.ShapeDtypeStruct((), jnp.int32)
    closed, out_shape = jax.make_jaxpr(program.body, return_shape=True)(
        *(it,) * plan.rank, env)
    leaves, tree = jax.tree_util.tree_flatten(out_shape)
    pos = jax.tree_util.tree_unflatten(tree, list(range(len(leaves))))
    return Body(closed, keys, {k: u.value for k, u in pos.items()})


@dataclasses.dataclass
class Src:
    """An env buffer as the evaluator sees it: ``win`` (the tile's region
    of a chunk window, or a forwarded tile; sharded axes lead and row 0
    of axis ``d`` is lane 0 shifted by ``origin[d]``), ``val`` (a
    replicated array) or ``zeros`` (a buffer the stage never reads).
    ``rows`` maps the k-space offsets ``(b, ...)`` of a ``val``'s reads
    ``x[i+b, ...]`` to the lanes' rows of it, each read served from its
    rows as a slice instead of a gather of the whole array."""

    kind: str
    value: Any = None
    origin: tuple = ()
    info: Any = None
    rows: dict | None = None


@dataclasses.dataclass
class _Read:
    """A served window read whose unit per-lane axes (the ``r`` sharded
    ones) are not materialised yet: the ``squeeze`` that jnp indexing
    emits next drops them for free."""

    value: Any
    mask: tuple
    r: int


def _is_var(v) -> bool:
    return isinstance(v, jcore.Var)


def _live_eqns(jaxpr, want, windows) -> set:
    live = {v for v in want if _is_var(v)}
    keep = set()
    for n in range(len(jaxpr.eqns) - 1, -1, -1):
        eqn = jaxpr.eqns[n]
        if not any(ov in live for ov in eqn.outvars):
            continue
        keep.add(n)
        ins = eqn.invars
        if eqn.primitive.name == "dynamic_slice" and ins[0] in windows:
            ins = ins[:1]                   # served as a static slice
        live.update(v for v in ins if _is_var(v))
    return keep


def _lane_affines(jaxpr, nax: int):
    """Affine tracking of a body's index arithmetic, iterator ``d``
    seeded as lane axis ``d``."""
    return ctx_mod._AffineEnv(
        {v: NestAffine(tuple(int(a == d) for a in range(nax)), 0)
         for d, v in enumerate(jaxpr.invars[:nax])},
        const=lambda c: NestAffine((0,) * nax, c))


def _apply_batched(eqn, ins, nax: int):
    """One equation over batched operands (leading dims = the batched
    lane axes, in axis order): nested ``jax.vmap``, outermost axis 0."""
    subfuns, params = eqn.primitive.get_bind_params(eqn.params)

    def f(*args):
        return eqn.primitive.bind(*subfuns, *args, **params)

    masks = [m for _, m in ins]
    out_mask = tuple(any(m[a] for m in masks) for a in range(nax))
    g = f
    for a in reversed(range(nax)):
        if out_mask[a]:
            g = jax.vmap(g, in_axes=tuple(0 if m[a] else None
                                          for m in masks))
    return g(*(x for x, _ in ins)), out_mask


def _unit_read(eqn, aff, plan, r: int, nax: int):
    """k-space offsets ``(b_0, ...)`` of ``dynamic_slice(x, i+b_0, ...)``
    whose leading ``r`` axes are unit reads that follow lane axis ``d``
    (``k_d + b_d``) and whose other axes start at constants; else
    ``None``."""
    starts = [aff.lookup(a) for a in eqn.invars[1:]]
    sizes = eqn.params["slice_sizes"]
    if len(starts) < r or any(m is None or not m.is_const
                              for m in starts[r:]):
        return None
    offs = []
    for d in range(r):
        km = starts[d].k_space(plan.nest) if starts[d] is not None else None
        if km is None or sizes[d] != 1 \
                or km.coeffs != tuple(int(a == d) for a in range(nax)):
            return None
        offs.append(km.b)
    return tuple(offs)


def _serve_read(eqn, src: Src, aff, plan, lanes):
    """``dynamic_slice(x, i+b, ...)`` of a window -> the tile's slice."""
    nax = len(lanes)
    r = len(src.origin)
    offs = _unit_read(eqn, aff, plan, r, nax)
    if offs is None:
        raise nest_mod.SubstitutionFailed(
            f"read of {eqn.invars[0]} is not a unit-stride read of the "
            "chunk window at constant unsharded positions")
    v = src.value
    for d in range(r):
        s = offs[d] - src.origin[d]
        if s < 0 or s + lanes[d] > v.shape[d]:
            raise nest_mod.SubstitutionFailed(
                f"read of axis {d} at offset {offs[d]} leaves the chunk "
                "window")
        v = jax.lax.slice_in_dim(v, s, s + lanes[d], axis=d)
    starts = [aff.lookup(a) for a in eqn.invars[1:]]
    sizes = eqn.params["slice_sizes"]
    for d in range(r, len(sizes)):
        size, dim = sizes[d], v.shape[d]
        if size != dim:
            c = min(max(starts[d].b, 0), dim - size)  # dynamic_slice clamps
            v = jax.lax.slice_in_dim(v, c, c + size, axis=d)
    v = v.astype(eqn.outvars[0].aval.dtype)
    return _Read(v, tuple(a < r for a in range(nax)), r)


def _materialize(rd: _Read):
    nb = sum(rd.mask)
    v = rd.value
    return v.reshape(v.shape[:nb] + (1,) * rd.r + v.shape[nb:]), rd.mask


def full(v, mask, lanes):
    """Broadcast a batched value over every lane axis."""
    v = jnp.asarray(v)
    for a, m in enumerate(mask):
        if not m:
            v = jnp.expand_dims(v, a)
    return jnp.broadcast_to(v, tuple(lanes) + v.shape[len(lanes):])


def eval_body(body: Body, plan, ivals, srcs, lanes, keys_out) -> dict:
    """Evaluate a loop body over one tile; returns ``key -> (value,
    batch mask)`` for every written key in ``keys_out``."""
    jaxpr = body.closed.jaxpr
    nax = len(lanes)
    none = (False,) * nax
    vals: dict = {}
    for v, c in zip(jaxpr.constvars, body.closed.consts):
        vals[v] = (c, none)
    for v, iv in zip(jaxpr.invars[:nax], ivals):
        vals[v] = iv
    src_of = dict(zip(jaxpr.invars[nax:], (srcs[k] for k in body.env_keys)))
    want = [jaxpr.outvars[body.value_pos[k]] for k in keys_out]
    windows = {v for v, src in src_of.items() if src.kind == "win"}
    live = _live_eqns(jaxpr, want, windows)
    aff = _lane_affines(jaxpr, nax)

    def read(v):
        if not _is_var(v):
            return v.val, none
        src = src_of.get(v)
        if src is not None:
            if src.kind == "val":
                return src.value, none
            if src.kind == "zeros":
                return jnp.zeros(src.info.shape, src.info.dtype), none
            raise nest_mod.SubstitutionFailed(
                "a chunk-window buffer is used other than through "
                "x[i]-style reads")
        got = vals[v]
        return _materialize(got) if isinstance(got, _Read) else got

    for n, eqn in enumerate(jaxpr.eqns):
        aff.process(eqn)
        if n not in live:
            continue
        prim = eqn.primitive.name
        x0 = eqn.invars[0] if eqn.invars else None
        if prim == "dynamic_slice" and x0 in windows:
            vals[eqn.outvars[0]] = _serve_read(eqn, src_of[x0], aff, plan,
                                               lanes)
            continue
        src = src_of.get(x0) if _is_var(x0) else None
        if prim == "dynamic_slice" and src is not None and src.rows:
            offs = _unit_read(eqn, aff, plan, nax, nax)
            if offs in src.rows:
                vals[eqn.outvars[0]] = _serve_read(
                    eqn, Src("win", src.rows[offs], offs), aff, plan, lanes)
                continue
        pending = vals.get(x0) if _is_var(x0) else None
        dims = eqn.params.get("dimensions", ())
        if prim == "squeeze" and isinstance(pending, _Read) \
                and set(range(pending.r)) <= set(dims):
            nb = sum(pending.mask)
            rest = tuple(nb + d - pending.r for d in dims if d >= pending.r)
            v = jax.lax.squeeze(pending.value, rest) if rest \
                else pending.value
            vals[eqn.outvars[0]] = (v, pending.mask)
            continue
        outs, mask = _apply_batched(eqn, [read(v) for v in eqn.invars], nax)
        if not eqn.primitive.multiple_results:
            outs = [outs]
        for ov, o in zip(eqn.outvars, outs):
            vals[ov] = (o, mask)
    return {k: read(w) for k, w in zip(keys_out, want)}


# ---------------------------------------------------------------------------
# A stage over its whole local chunk stack (the lax lowering)
# ---------------------------------------------------------------------------


def local_chunk_ids(ch, device_index):
    """Global chunk id of each of this device's local chunks: ``q*P + d``
    for the cyclic deal, the plan's slot map for a weighted one."""
    if ch.slot_map is None:
        return (jnp.arange(ch.local_chunks, dtype=jnp.int32)
                * ch.num_devices + device_index)
    table = jnp.asarray(np.asarray(ch.slot_map, dtype=np.int32).reshape(
        ch.local_chunks, ch.num_devices))
    return jax.lax.dynamic_index_in_dim(table, device_index, 1,
                                        keepdims=False)


def window_origin(dec, axis: int = 0) -> int:
    """k-space offset of row 0 of a chunk window on ``axis``, from the
    chunk's first iteration."""
    if dec.in_strategy != "shard_halo":
        return 0
    if dec.halo_axes is not None:
        return dec.halo_axes[axis][0]
    return dec.halo[0] if dec.halo is not None else 0


def _read_offsets(body, plan, keys) -> dict:
    """Key -> the k-space offsets of its reads that :func:`_unit_read`
    accepts, for the replicated buffers in ``keys``."""
    jaxpr = body.closed.jaxpr
    nax = plan.rank
    key_of = dict(zip(jaxpr.invars[nax:], body.env_keys))
    aff = _lane_affines(jaxpr, nax)
    found: dict = {}
    for eqn in jaxpr.eqns:
        aff.process(eqn)
        x0 = eqn.invars[0] if eqn.invars else None
        if eqn.primitive.name == "dynamic_slice" and _is_var(x0) \
                and key_of.get(x0) in keys:
            offs = _unit_read(eqn, aff, plan, nax, nax)
            if offs is not None:
                found.setdefault(key_of[x0], set()).add(offs)
    return found


def _local_rows(x, chs, offsets, device_indices):
    """``(n_0, c_0[, n_1, c_1], *rest)``: ``x[k_0 + b_0(, k_1 + b_1)]``
    at every lane of this device's local chunks, zero outside ``x``.
    Padding first keeps every slice start in place: the slices never
    clamp, the last (padded) chunk's included."""
    for a, (ch, b, d) in enumerate(zip(chs, offsets, device_indices)):
        pos = 2 * a                   # earlier axes are split in two
        total = ch.num_chunks * ch.chunk
        lo, hi = max(0, -b), max(0, b + total - x.shape[pos])
        pad = [(0, 0)] * x.ndim
        pad[pos] = (lo, hi)
        x = jax.lax.slice_in_dim(jnp.pad(x, pad), lo + b, lo + b + total,
                                 axis=pos)
        if ch.slot_map is None:
            x = x.reshape(x.shape[:pos] + (ch.local_chunks, ch.num_devices,
                                           ch.chunk) + x.shape[pos + 1:])
            x = jax.lax.dynamic_index_in_dim(x, d, pos + 1, keepdims=False)
        else:
            x = x.reshape(x.shape[:pos] + (ch.num_chunks, ch.chunk)
                          + x.shape[pos + 1:])
            x = jnp.take(x, local_chunk_ids(ch, d), axis=pos)
    return x


def eval_local_chunks(plan, program, env_in, slab_stacks,
                      device_indices) -> dict:
    """Dense body values ``key -> (n_0, c_0[, n_1, c_1], *value)`` of
    every local chunk (rank 1) or ``(chunk_i, chunk_j)`` pair (rank 2)
    of a stage.

    The body is evaluated once, with lanes ``(c_0[, c_1])``, and mapped
    over each axis's stack of the windows ``(n_0, w_0[, n_1, w_1],
    *rest)`` (``(n_0, w_0, *rest)`` for a 1-D slab of a rank-2 stage),
    the first axis by a loop where the stage reads through an index
    array (:func:`_loop_chunks`): a static slice of a mapped window stays
    a slice.  A unit-stride read
    ``x[i+b(, j+c)]`` of a replicated buffer is served the same way,
    from rows of it cut for the local chunks before the map; any other
    read of one (through an index array, say) stays a gather.  Padded
    lanes read beyond the last iteration instead of clamping; the merge
    masks them and the exit crops them.  Raises ``SubstitutionFailed``
    where a window read is not a unit-stride ``x[i+b(, j+c)]`` inside
    the window."""
    body = trace_body(plan, program)
    chs = plan.chunks_axes
    rank = len(chs)
    lanes = tuple(ch.chunk for ch in chs)
    keys_out = [k for k in sorted(plan.vars)
                if plan.vars[k].out_strategy != "none"]
    # per-axis iterator values (n_a, c_a), clamped like the trip padding
    ivals = []
    for ch, loop, d in zip(chs, plan.nest.axes, device_indices):
        ks = (local_chunk_ids(ch, d)[:, None] * ch.chunk
              + jnp.arange(ch.chunk, dtype=jnp.int32)[None, :])
        kc = jnp.minimum(ks, max(0, loop.trip_count - 1))
        ivals.append(loop.start + loop.step * kc)
    replicated = {k for k in body.env_keys
                  if plan.vars[k].in_strategy == "replicate"}
    rows = {(k, offs): _local_rows(env_in[k], chs, offs, device_indices)
            for k, found in _read_offsets(body, plan, replicated).items()
            for offs in sorted(found)}

    def stacked(key):                # the window axes that carry a stack
        return plan.vars[key].shard_ndim if rank == 2 else 1

    def one(ivs, wins_q, rows_q):
        srcs = {}
        for key in body.env_keys:
            dec = plan.vars[key]
            if key in wins_q:
                srcs[key] = Src("win", wins_q[key], tuple(
                    window_origin(dec, a) for a in range(stacked(key))))
            elif key in replicated:
                srcs[key] = Src("val", env_in[key], rows={
                    offs: v for (k, offs), v in rows_q.items() if k == key})
            else:
                srcs[key] = Src("zeros", info=plan.context.vars[key])
        got = eval_body(body, plan, tuple(
            (iv, tuple(b == a for b in range(rank)))
            for a, iv in enumerate(ivs)), srcs, lanes, keys_out)
        return {k: full(*got[k], lanes).astype(
            plan.context.vars[k].write.value_dtype) for k in keys_out}

    # map the innermost stack axis first: once axes < a are mapped, the
    # stack of axis a is axis a of a window and of a row stack, and its
    # values go between c_{a-1} and c_a: (n_0, c_0, n_1, c_1, *value)
    f = one
    for a in reversed(range(rank)):
        if a == 0 and plan.indirect_reads:
            f = _loop_chunks(f)
        else:
            f = jax.vmap(f, out_axes=a, in_axes=(
                tuple(0 if b == a else None for b in range(rank)),
                {k: a if a < stacked(k) else None for k in slab_stacks},
                {kb: a for kb in rows}))
    return f(tuple(ivals), slab_stacks, rows)


def _loop_chunks(f):
    """``f`` mapped over the first stack axis by a loop, one chunk at a
    time.  A stage that gathers through an index array takes it: a
    ``vmap`` over the stack would hold every chunk's ``(lanes, index
    entries)`` gather at once, where the loop holds one chunk's."""
    def over(ivs, wins, rows):
        return jax.lax.map(lambda q: f((q[0],) + ivs[1:], q[1], q[2]),
                           (ivs[0], wins, rows))
    return over


# ---------------------------------------------------------------------------
# Merge — reproducing the chunk scans' (carry, ys) contract from dense
# per-lane values
# ---------------------------------------------------------------------------


def merge_chunk_values(plan, values, device_indices):
    """``(n_0, c_0[, n_1, c_1], *value_shape)`` dense values -> ``(carry,
    ys)`` exactly as the scan of vmapped chunks (``_scan_local_chunks``
    / ``_scan_local_chunks2`` in :mod:`repro.core.transform`) would have
    produced them.  ``scatter`` and ``put`` writes exist at rank 1."""
    chs = plan.chunks_axes
    rank = len(chs)
    ids = [local_chunk_ids(ch, d) for ch, d in zip(chs, device_indices)]
    valid = None
    for a, (ch, loop, js) in enumerate(zip(chs, plan.nest.axes, ids)):
        ks = (js[:, None] * ch.chunk
              + jnp.arange(ch.chunk, dtype=jnp.int32)[None, :])
        ok = (ks < loop.trip_count).reshape(
            (1, 1) * a + ks.shape + (1, 1) * (rank - 1 - a))
        valid = ok if valid is None else valid & ok
    carry: dict[str, Any] = {}
    ys: dict[str, Any] = {}
    for key, dec in plan.vars.items():
        if dec.out_strategy == "none":
            continue
        v = values[key]
        info = plan.context.vars[key]
        if dec.out_strategy in ("identity", "partial"):
            ys[key] = v
        elif dec.out_strategy == "scatter":
            shape0 = info.shape[0]
            pos = dec.write_map.a * ks + dec.write_map.b
            pos = jnp.where(valid, pos, shape0).reshape(-1)
            flat = v.reshape((-1,) + v.shape[2:])
            buf = jnp.zeros(info.shape, info.dtype) \
                .at[pos].set(flat, mode="drop")
            mask = jnp.zeros((shape0,), jnp.bool_) \
                .at[pos].set(True, mode="drop")
            carry[key] = (buf, mask)
        elif dec.out_strategy == "put":
            ch, (js,) = chs[0], ids
            t = plan.loop.trip_count
            j_star = (t - 1) // ch.chunk
            lane = (t - 1) - j_star * ch.chunk
            q_star = (j_star // ch.num_devices if ch.slot_map is None
                      else jnp.argmax(js == j_star))
            row = v[q_star, lane]
            carry[key] = jnp.where(js[q_star] == j_star, row,
                                   jnp.zeros(info.shape, info.dtype))
        elif dec.out_strategy == "reduce":
            rop = red_mod.get_reduction(dec.reduction_op)
            ident = red_mod.identity_like(rop, v)
            vmask = valid.reshape(valid.shape + (1,) * (v.ndim - 2 * rank))
            flat = jnp.where(vmask, v, ident) \
                .reshape((-1,) + v.shape[2 * rank:])
            carry0 = red_mod.identity_like(
                rop, jnp.zeros(info.write.value_shape,
                               info.write.value_dtype))
            carry[key] = rop.pairwise(carry0, rop.local_fold(flat, 0))
    return carry, ys
