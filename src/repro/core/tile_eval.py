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
* :mod:`repro.core.transform` — a rank-2 stage's whole local chunk
  stack at once (:func:`eval_local_chunks2`), mapped over the stack axes
  so every window read stays a slice.

Both hand the dense per-lane values to :func:`merge_chunk_values` /
:func:`merge_chunk_values2`, which rebuild the ``(carry, ys)`` contract
of the chunk scans in :mod:`repro.core.transform`.  A body whose window
reads are not unit-stride reads inside the window raises
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
    replicated array) or ``zeros`` (a buffer the stage never reads)."""

    kind: str
    value: Any = None
    origin: tuple = ()
    info: Any = None


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


def _serve_read(eqn, src: Src, aff, plan, lanes):
    """``dynamic_slice(x, i+b, ...)`` of a window -> the tile's slice."""
    nax = len(lanes)
    starts = [aff.lookup(a) for a in eqn.invars[1:]]
    sizes = eqn.params["slice_sizes"]
    r = len(src.origin)
    v = src.value
    for d in range(r):
        unit = tuple(int(a == d) for a in range(nax))
        km = starts[d].k_space(plan.nest) if starts[d] is not None else None
        s = None if km is None or km.coeffs != unit else km.b - src.origin[d]
        if sizes[d] != 1 or s is None or s < 0 \
                or s + lanes[d] > v.shape[d]:
            raise nest_mod.SubstitutionFailed(
                f"read of axis {d} at {starts[d]!r} is not a unit-stride "
                "read inside the chunk window")
        v = jax.lax.slice_in_dim(v, s, s + lanes[d], axis=d)
    for d in range(r, len(sizes)):
        m, size, dim = starts[d], sizes[d], v.shape[d]
        if m is None or not m.is_const:
            raise nest_mod.SubstitutionFailed(
                f"read of unsharded axis {d} at {m!r} is not constant")
        if size != dim:
            c = min(max(m.b, 0), dim - size)    # dynamic_slice clamps
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
    aff = ctx_mod._AffineEnv(
        {v: NestAffine(tuple(int(a == d) for a in range(nax)), 0)
         for d, v in enumerate(jaxpr.invars[:nax])},
        const=lambda c: NestAffine((0,) * nax, c))

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
# A rank-2 stage over its whole local chunk stack (the lax lowering)
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


def eval_local_chunks2(plan, program, env_in, slab_stacks,
                       device_indices) -> dict:
    """Dense body values ``key -> (n_i, c_i, n_j, c_j, *value)`` of every
    local ``(chunk_i, chunk_j)`` pair of a rank-2 stage.

    The body is evaluated once, with lanes ``(c_i, c_j)``, and mapped
    over the stack axes of the windows ``(n_i, w_i, n_j, w_j, *rest)``
    (``(n_i, w_i, *rest)`` for a 1-D slab): a static slice of a mapped
    window stays a slice.  Padded lanes read the window beyond the last
    iteration instead of clamping; the merge masks them and the exit
    crops them.  Raises ``SubstitutionFailed`` where a window read is not
    a unit-stride ``x[i+b, j+c]`` inside the window."""
    body = trace_body(plan, program)
    chs = plan.chunks_axes
    lanes = tuple(ch.chunk for ch in chs)
    keys_out = [k for k in sorted(plan.vars)
                if plan.vars[k].out_strategy != "none"]
    # per-axis iterator values (n_q, c), clamped like the trip padding
    ivals = []
    for ch, loop, d in zip(chs, plan.nest.axes, device_indices):
        ks = (local_chunk_ids(ch, d)[:, None] * ch.chunk
              + jnp.arange(ch.chunk, dtype=jnp.int32)[None, :])
        kc = jnp.minimum(ks, max(0, loop.trip_count - 1))
        ivals.append(loop.start + loop.step * kc)

    def pair(iv, jv, wins_q):
        srcs = {}
        for key in body.env_keys:
            dec = plan.vars[key]
            if key in wins_q:
                srcs[key] = Src("win", wins_q[key], tuple(
                    h[0] for h in dec.halo_axes[:dec.shard_ndim]))
            elif dec.in_strategy == "replicate":
                srcs[key] = Src("val", env_in[key])
            else:
                srcs[key] = Src("zeros", info=plan.context.vars[key])
        got = eval_body(body, plan, ((iv, (True, False)),
                                     (jv, (False, True))),
                        srcs, lanes, keys_out)
        return {k: full(*got[k], lanes).astype(
            plan.context.vars[k].write.value_dtype) for k in keys_out}

    # inner map over n_j (axis 1 of a 2-D window once n_i is mapped),
    # its values placed between c_i and c_j: (n_i, c_i, n_j, c_j, *value)
    over_j = jax.vmap(pair, out_axes=1, in_axes=(None, 0, {
        k: 1 if plan.vars[k].shard_ndim == 2 else None
        for k in slab_stacks}))
    over_i = jax.vmap(over_j, in_axes=(0, None, {k: 0 for k in slab_stacks}))
    return over_i(ivals[0], ivals[1], slab_stacks)


# ---------------------------------------------------------------------------
# Merges — reproducing the _run_local_chunks / _run_local_chunks2
# (carry, ys) contract from dense per-lane values
# ---------------------------------------------------------------------------


def merge_chunk_values(plan, values, device_index):
    """(n_loc, c, *value_shape) dense values -> (carry, ys) exactly as
    ``_run_local_chunks`` would have produced them."""
    ch = plan.chunks
    t = plan.loop.trip_count
    js = (jnp.arange(ch.local_chunks, dtype=jnp.int32) * ch.num_devices
          + device_index)
    ks = (js[:, None] * ch.chunk
          + jnp.arange(ch.chunk, dtype=jnp.int32)[None, :])
    valid = ks < t
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
            j_star = (t - 1) // ch.chunk
            lane = (t - 1) - j_star * ch.chunk
            q_star = j_star // ch.num_devices
            row = v[q_star, lane]
            carry[key] = jnp.where(js[q_star] == j_star, row,
                                   jnp.zeros(info.shape, info.dtype))
        elif dec.out_strategy == "reduce":
            rop = red_mod.get_reduction(dec.reduction_op)
            ident = red_mod.identity_like(rop, v)
            vmask = valid.reshape(valid.shape + (1,) * (v.ndim - 2))
            flat = jnp.where(vmask, v, ident) \
                .reshape((-1,) + v.shape[2:])
            carry0 = red_mod.identity_like(
                rop, jnp.zeros(info.write.value_shape,
                               info.write.value_dtype))
            carry[key] = rop.pairwise(carry0, rop.local_fold(flat, 0))
    return carry, ys


def merge_chunk_values2(plan, values, device_indices):
    """(n_i, c_i, n_j, c_j, *value_shape) dense values -> (carry, ys)
    exactly as ``_run_local_chunks2`` would have produced them."""
    ch_i, ch_j = plan.chunks_axes
    loop_i, loop_j = plan.nest.axes
    d_i, d_j = device_indices
    ks_i = (local_chunk_ids(ch_i, d_i)[:, None] * ch_i.chunk
            + jnp.arange(ch_i.chunk, dtype=jnp.int32)[None, :])
    ks_j = (local_chunk_ids(ch_j, d_j)[:, None] * ch_j.chunk
            + jnp.arange(ch_j.chunk, dtype=jnp.int32)[None, :])
    valid = (ks_i < loop_i.trip_count)[:, :, None, None] \
        & (ks_j < loop_j.trip_count)[None, None, :, :]
    carry: dict[str, Any] = {}
    ys: dict[str, Any] = {}
    for key, dec in plan.vars.items():
        if dec.out_strategy == "none":
            continue
        v = values[key]
        info = plan.context.vars[key]
        if dec.out_strategy in ("identity", "partial"):
            ys[key] = v
        elif dec.out_strategy == "reduce":
            rop = red_mod.get_reduction(dec.reduction_op)
            ident = red_mod.identity_like(rop, v)
            vmask = valid.reshape(valid.shape + (1,) * (v.ndim - 4))
            flat = jnp.where(vmask, v, ident) \
                .reshape((-1,) + v.shape[4:])
            carry0 = red_mod.identity_like(
                rop, jnp.zeros(info.write.value_shape,
                               info.write.value_dtype))
            carry[key] = rop.pairwise(carry0, rop.local_fold(flat, 0))
    return carry, ys
