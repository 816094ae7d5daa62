"""Context Analysis (paper §3.1.1).

OMP2MPI walks the Mercurium AST to classify every shared variable used in
an OpenMP block as IN (read, never written), OUT (written, consumed after
the block) or INOUT (both), and works out *where* the parallel iterator
appears in each array access (the "linear first-dimension" rule of
§3.1.3).  The JAX analogue walks the **jaxpr** of the loop body:

* reads are recovered from how each ``env`` buffer's invar is consumed —
  an invar whose every use is a ``dynamic_slice`` whose leading start
  index is an *affine* function of the iterator is a sliced read
  (``x[a*i+b]``); a read whose indices come from a sliced read of an
  integer buffer (``x[idx[i]]``) is an indirect read through that
  buffer; any other use makes it a whole-array read;
* writes are the declared :class:`~repro.core.pragma.At`/``Put``/``Red``
  updates; ``At`` indices are checked for affinity by symbolic affine
  propagation through the jaxpr (add/sub/mul/neg/convert chains seeded at
  the iterator invar).

The affine tracker also understands the negative-index wrap pattern jnp
emits for ``x[i]`` (``select_n(i < 0, i, i + dim)``): assuming a
non-negative iteration space it resolves to the raw affine index.
"""
from __future__ import annotations

import dataclasses
import enum
import math
from typing import Any, Mapping

import jax
import jax.numpy as jnp
from jax.extend import core as jcore

from repro.core import pragma
from repro.core.loop import LoopInfo, LoopNotCanonical
from repro.core.nest import LoopNest, NestAffine


# ---------------------------------------------------------------------------
# Affine expressions over the loop iterator
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Affine:
    """``a * i + b`` with static integer coefficients."""

    a: int
    b: int

    def __add__(self, other: "Affine") -> "Affine":
        return Affine(self.a + other.a, self.b + other.b)

    def __sub__(self, other: "Affine") -> "Affine":
        return Affine(self.a - other.a, self.b - other.b)

    def scale(self, k: int) -> "Affine":
        return Affine(self.a * k, self.b * k)

    @property
    def is_const(self) -> bool:
        return self.a == 0

    def __repr__(self) -> str:
        if self.a == 0:
            return str(self.b)
        s = "i" if self.a == 1 else f"{self.a}*i"
        return s if self.b == 0 else f"{s}{self.b:+d}"


def _literal_int(x: Any) -> int | None:
    try:
        v = int(x)
    except (TypeError, ValueError):
        return None
    if jnp.ndim(x) != 0:
        return None
    return v


def _literal_affine(x: Any) -> Affine | None:
    v = _literal_int(x)
    return None if v is None else Affine(0, v)


class _AffineEnv:
    """Symbolic affine propagation over jaxpr equations.

    Works over any affine representation supporting ``+``/``-``/
    ``scale``/``is_const``/``.b`` — :class:`Affine` for a single
    iterator, :class:`~repro.core.nest.NestAffine` for a loop nest.
    ``seeds`` maps iterator invars to their affine seeds; ``const``
    builds a constant of the same representation.
    """

    def __init__(self, seeds: Mapping[Any, Any],
                 const: Any = None) -> None:
        self._map: dict[Any, Any] = dict(seeds)
        self._const = const or (lambda v: Affine(0, v))
        self._producer: dict[Any, Any] = {}

    def lookup(self, atom):
        if isinstance(atom, jcore.Literal):
            v = _literal_int(atom.val)
            return None if v is None else self._const(v)
        return self._map.get(atom)

    def process(self, eqn) -> None:
        prim = eqn.primitive.name
        outs = eqn.outvars
        for ov in outs:
            self._producer[ov] = eqn
        if len(outs) != 1:
            return
        out = outs[0]
        # Only scalar integer-ish values can be loop indices.
        if getattr(out.aval, "shape", None) not in ((),):
            return
        ins = [self.lookup(v) for v in eqn.invars]
        res: Affine | None = None
        if prim == "add" and None not in ins:
            res = ins[0] + ins[1]
        elif prim == "sub" and None not in ins:
            res = ins[0] - ins[1]
        elif prim == "mul" and None not in ins:
            if ins[0].is_const:
                res = ins[1].scale(ins[0].b)
            elif ins[1].is_const:
                res = ins[0].scale(ins[1].b)
        elif prim == "neg" and ins[0] is not None:
            res = ins[0].scale(-1)
        elif prim in ("convert_element_type", "copy", "squeeze", "stop_gradient"):
            res = ins[0]
        elif prim == "max" and None not in ins:
            # clamp(i, 0) pattern: max(i, 0) with nonneg iteration space.
            if ins[0].is_const and ins[0].b == 0:
                res = ins[1]
            elif ins[1].is_const and ins[1].b == 0:
                res = ins[0]
        elif prim == "select_n" and len(eqn.invars) == 3:
            res = self._wrap_pattern(eqn)
        if res is not None:
            self._map[out] = res

    def _wrap_pattern(self, eqn) -> Affine | None:
        """Resolve ``select_n(v < 0, v, v + dim)`` → affine(v)."""
        pred, case_f, case_t = eqn.invars
        pred_eqn = self._producer.get(pred)
        if pred_eqn is None or pred_eqn.primitive.name != "lt":
            return None
        lhs, rhs = pred_eqn.invars
        rhs_aff = self.lookup(rhs)
        if rhs_aff is None or not rhs_aff.is_const or rhs_aff.b != 0:
            return None
        # The non-negative branch is the lt's lhs; pick whichever case is it.
        for case in (case_f, case_t):
            if case is lhs:
                return self.lookup(case)
        # jnp sometimes converts dtype between lt and select; fall back to
        # the case whose affine matches lhs's affine exactly.
        lhs_aff = self.lookup(lhs)
        if lhs_aff is None:
            return None
        for case in (case_f, case_t):
            if self.lookup(case) == lhs_aff:
                return lhs_aff
        return None


# ---------------------------------------------------------------------------
# Classification results
# ---------------------------------------------------------------------------


class ReadKind(enum.Enum):
    NONE = "none"
    SLICED = "sliced"    # every use is x[a*i+b] on the leading dim
    STENCIL = "stencil"  # several unit-stride maps x[i+b0..i+bk] (halo)
    WHOLE = "whole"
    INDIRECT = "indirect"  # x[idx[i]]: gathered through a sliced int read


class WriteKind(enum.Enum):
    NONE = "none"
    AT = "at"
    PUT = "put"
    RED = "red"


class VarClass(enum.Enum):
    UNUSED = "unused"
    IN = "in"
    OUT = "out"
    INOUT = "inout"
    REDUCTION = "reduction"


@dataclasses.dataclass
class ReadInfo:
    kind: ReadKind
    affine: Affine | None = None          # leading-dim index map for SLICED
    affines: list | None = None           # all maps for STENCIL reads
    # rank-2 nests: number of leading buffer axes read through unit
    # slices, and the distinct per-axis NestAffine index tuples
    slice_ndim: int = 0
    accesses: tuple | None = None
    # INDIRECT reads: the index buffer, and the entries one iteration
    # looks up through it
    index_key: str | None = None
    index_elems: int = 0


@dataclasses.dataclass
class WriteInfo:
    kind: WriteKind
    affine: Affine | None = None          # index map for AT (None: non-affine)
    value_shape: tuple[int, ...] = ()
    value_dtype: Any = None
    reduction_op: str | None = None
    # rank-2 nests: per-buffer-axis NestAffine maps of the At index
    # tuple (entries None where non-affine)
    affines2: tuple | None = None


@dataclasses.dataclass
class VarInfo:
    name: str
    read: ReadInfo
    write: WriteInfo
    klass: VarClass
    shape: tuple[int, ...] = ()
    dtype: Any = None


@dataclasses.dataclass
class ContextInfo:
    """Output of the Context Analysis stage for one parallel block."""

    vars: dict[str, VarInfo]
    env_keys: list[str]
    update_keys: list[str]

    def by_class(self, klass: VarClass) -> list[str]:
        return [k for k, v in self.vars.items() if v.klass == klass]


# ---------------------------------------------------------------------------
# Analysis driver
# ---------------------------------------------------------------------------

# Primitives an index vector passes through between its sliced read
# (``idx[i]``) and the gather it feeds: jnp's negative-index wrap
# (``lt``/``add``/``select_n``), reshapes and dtype converts.
_INDEX_PRIMS = frozenset({
    "squeeze", "reshape", "broadcast_in_dim", "expand_dims", "slice",
    "convert_element_type", "copy", "lt", "add", "sub", "select_n",
    "max", "min", "clamp"})


def _source(sources, atom) -> str | None:
    return None if isinstance(atom, jcore.Literal) else sources.get(atom)


def _index_source(eqn, sources, aff) -> str | None:
    """The index buffer that every non-constant operand of an
    index-arithmetic ``eqn`` was read from, or ``None``."""
    if eqn.primitive.name not in _INDEX_PRIMS or len(eqn.outvars) != 1:
        return None
    keys = set()
    for iv in eqn.invars:
        if isinstance(iv, jcore.Literal):
            continue
        if iv in sources:
            keys.add(sources[iv])
        elif (a := aff.lookup(iv)) is None or not a.is_const:
            return None
    return keys.pop() if len(keys) == 1 else None


def _aval_of(x: Any) -> jax.ShapeDtypeStruct:
    if isinstance(x, jax.ShapeDtypeStruct):
        return x
    arr = jnp.asarray(x)
    return jax.ShapeDtypeStruct(arr.shape, arr.dtype)


def analyze_context(program: pragma.ParallelFor, env: Mapping[str, Any],
                    loop: LoopInfo | LoopNest) -> ContextInfo:
    """Run the Context Analysis stage: trace the body once with an abstract
    iterator per nest axis, then classify every env buffer from the jaxpr."""
    if isinstance(loop, LoopNest):
        if loop.rank == 2:
            return _analyze_context2(program, env, loop)
        loop = loop.axes[0]
    env_keys = list(env.keys())
    env_avals = {k: _aval_of(v) for k, v in env.items()}

    def traced(i, env_arrays):
        return program.body(i, env_arrays)

    i_aval = jax.ShapeDtypeStruct((), jnp.int32)
    closed, out_shape = jax.make_jaxpr(traced, return_shape=True)(i_aval, env_avals)
    jaxpr = closed.jaxpr

    # --- map env keys to invars -------------------------------------------
    # Dicts flatten in sorted-key order; each env value must be one array.
    env_leaves, _ = jax.tree_util.tree_flatten(env_avals)
    n_env = len(env_leaves)
    sorted_keys = sorted(env_avals.keys())
    if n_env != len(sorted_keys):
        raise LoopNotCanonical("env values must be single arrays (no nested pytrees)")
    if len(jaxpr.invars) != 1 + n_env:
        raise LoopNotCanonical(
            "body must take (i, env) with env a flat dict of arrays; got "
            f"{len(jaxpr.invars)} invars for {n_env} env leaves"
        )
    iter_var = jaxpr.invars[0]
    var_of_key = {k: jaxpr.invars[1 + pos] for pos, k in enumerate(sorted_keys)}
    key_of_var = {id(v): k for k, v in var_of_key.items()}

    # --- affine propagation + read usage scan ------------------------------
    aff = _AffineEnv({iter_var: Affine(1, 0)})
    # read bookkeeping: key -> list of (eqn, affine-or-None) slice uses,
    # plus a flag for non-slice uses.
    sliced_uses: dict[str, list[Affine | None]] = {k: [] for k in env_keys}
    whole_use: dict[str, bool] = {k: False for k in env_keys}
    # indirect reads: key -> [(index key, entries looked up)]; sources
    # maps each value computed from a sliced read of an integer buffer
    # (and constants) to that buffer's key
    indirect_uses: dict[str, list[tuple[str, int]]] = {
        k: [] for k in env_keys}
    sources: dict[Any, str] = {}

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        for pos, iv in enumerate(eqn.invars):
            key = key_of_var.get(id(iv))
            if key is None:
                continue
            if prim == "dynamic_slice" and pos == 0:
                idx_atoms = eqn.invars[1:]
                sizes = eqn.params["slice_sizes"]
                shape = env_avals[key].shape
                lead = aff.lookup(idx_atoms[0]) if idx_atoms else None
                rest_ok = all(
                    (a := aff.lookup(at)) is not None and a.is_const
                    for at in idx_atoms[1:]
                )
                shape_ok = sizes and sizes[0] == 1 and len(shape) == len(sizes)
                via = _source(sources, idx_atoms[0]) if idx_atoms else None
                if lead is not None and shape_ok and rest_ok:
                    sliced_uses[key].append(lead)
                    if jnp.issubdtype(env_avals[key].dtype, jnp.integer):
                        sources[eqn.outvars[0]] = key
                elif via is not None and via != key and shape_ok and rest_ok:
                    indirect_uses[key].append((via, 1))
                else:
                    whole_use[key] = True
            elif (prim == "gather" and pos == 0
                    and _source(sources, eqn.invars[1]) not in (None, key)):
                # one index tuple per entry of all but the index-vector axis
                n = math.prod(eqn.invars[1].aval.shape[:-1])
                indirect_uses[key].append((sources[eqn.invars[1]], n))
            else:
                whole_use[key] = True
        via = _index_source(eqn, sources, aff)
        if via is not None:
            sources[eqn.outvars[0]] = via
        aff.process(eqn)

    # --- write classification from the returned update structure -----------
    flat_shapes, out_tree = jax.tree_util.tree_flatten(out_shape)
    positions = jax.tree_util.tree_unflatten(out_tree, list(range(len(flat_shapes))))
    outvars = jaxpr.outvars
    if not isinstance(positions, dict):
        raise LoopNotCanonical("body must return a dict of omp updates")

    writes: dict[str, WriteInfo] = {}
    for key, upd in positions.items():
        if isinstance(upd, pragma.At):
            idx_pos, val_pos = upd.idx, upd.value
            idx_atom = outvars[idx_pos]
            write_aff = (
                _literal_affine(idx_atom.val)
                if isinstance(idx_atom, jcore.Literal)
                else aff.lookup(idx_atom)
            )
            vshape = flat_shapes[val_pos]
            writes[key] = WriteInfo(
                WriteKind.AT,
                affine=write_aff,
                value_shape=tuple(vshape.shape),
                value_dtype=vshape.dtype,
            )
        elif isinstance(upd, pragma.Put):
            vshape = flat_shapes[upd.value]
            writes[key] = WriteInfo(
                WriteKind.PUT,
                value_shape=tuple(vshape.shape),
                value_dtype=vshape.dtype,
            )
        elif isinstance(upd, pragma.Red):
            if key not in program.reduction:
                raise LoopNotCanonical(
                    f"omp.red() for {key!r} without a reduction clause "
                    "(paper: reductions must be declared with reduction(op: var))"
                )
            vshape = flat_shapes[upd.value]
            writes[key] = WriteInfo(
                WriteKind.RED,
                value_shape=tuple(vshape.shape),
                value_dtype=vshape.dtype,
                reduction_op=program.reduction[key],
            )
        else:
            raise LoopNotCanonical(
                f"update for {key!r} must be omp.at/omp.put/omp.red, got "
                f"{type(upd).__name__}"
            )

    for key in program.reduction:
        if key in writes and writes[key].kind != WriteKind.RED:
            raise LoopNotCanonical(
                f"{key!r} is declared as a reduction but written with "
                f"{writes[key].kind.value}"
            )

    # --- assemble per-variable classification ------------------------------
    infos: dict[str, VarInfo] = {}
    all_keys = list(env_keys) + [k for k in writes if k not in env_avals]
    for key in all_keys:
        if key in env_avals:
            shape, dtype = env_avals[key].shape, env_avals[key].dtype
        else:
            # Reduction outputs may be fresh (not pre-existing in env).
            w = writes[key]
            shape, dtype = w.value_shape, w.value_dtype
        via = {k for k, _ in indirect_uses.get(key, ())}
        if key in env_avals and (whole_use[key] or len(via) > 1):
            read = ReadInfo(ReadKind.WHOLE)
        elif via:
            # replicated like a whole read; its sliced uses read that copy
            read = ReadInfo(ReadKind.INDIRECT, index_key=via.pop(),
                            index_elems=sum(n for _, n in indirect_uses[key]))
        elif key in env_avals and sliced_uses[key]:
            affs = sliced_uses[key]
            if any(a is None for a in affs):
                read = ReadInfo(ReadKind.WHOLE)
            elif len({(a.a, a.b) for a in affs}) == 1:
                read = ReadInfo(ReadKind.SLICED, affs[0])
            elif all(a.a == affs[0].a for a in affs):
                # several unit-stride maps (x[i-1], x[i], x[i+1]):
                # a stencil — distributable with a halo exchange
                uniq = sorted({(a.a, a.b) for a in affs},
                              key=lambda t: t[1])
                read = ReadInfo(ReadKind.STENCIL, affs[0],
                                [Affine(a, b) for a, b in uniq])
            else:
                read = ReadInfo(ReadKind.WHOLE)
        else:
            read = ReadInfo(ReadKind.NONE)

        write = writes.get(key, WriteInfo(WriteKind.NONE))
        if write.kind == WriteKind.RED:
            klass = VarClass.REDUCTION
        elif write.kind == WriteKind.NONE:
            klass = VarClass.IN if read.kind != ReadKind.NONE else VarClass.UNUSED
        elif read.kind == ReadKind.NONE:
            klass = VarClass.OUT
        else:
            klass = VarClass.INOUT
        infos[key] = VarInfo(
            name=key, read=read, write=write, klass=klass,
            shape=tuple(shape), dtype=dtype,
        )

    return ContextInfo(vars=infos, env_keys=env_keys, update_keys=list(writes))


# ---------------------------------------------------------------------------
# Rank-2 nest driver (``collapse=2``)
# ---------------------------------------------------------------------------


def _access_prefix(starts, sizes, shape) -> int:
    """Largest sliced prefix r in {1, 2} this dynamic_slice supports:
    axes d < r are unit slices with affine starts; axes d >= r are
    whole-axis slices (const-0 start) or const unit slices.  0 = neither.
    """
    def suffix_ok(d0: int) -> bool:
        for d in range(d0, len(shape)):
            a = starts[d]
            if a is None:
                return False
            if sizes[d] == shape[d] and a.is_const and a.b == 0:
                continue
            if sizes[d] == 1 and a.is_const:
                continue
            return False
        return True

    for r in (2, 1):
        if len(shape) < r or len(sizes) != len(shape):
            continue
        if all(sizes[d] == 1 and starts[d] is not None for d in range(r)) \
                and suffix_ok(r):
            return r
    return 0


def _analyze_context2(program: pragma.ParallelFor, env: Mapping[str, Any],
                      nest: LoopNest) -> ContextInfo:
    """Context Analysis over a rank-2 nest: the body is traced as
    ``body(i, j, env)`` and every index is tracked as a
    :class:`~repro.core.nest.NestAffine` over both iterators."""
    env_keys = list(env.keys())
    env_avals = {k: _aval_of(v) for k, v in env.items()}

    def traced(i, j, env_arrays):
        return program.body(i, j, env_arrays)

    it_aval = jax.ShapeDtypeStruct((), jnp.int32)
    closed, out_shape = jax.make_jaxpr(traced, return_shape=True)(
        it_aval, it_aval, env_avals)
    jaxpr = closed.jaxpr

    env_leaves, _ = jax.tree_util.tree_flatten(env_avals)
    n_env = len(env_leaves)
    sorted_keys = sorted(env_avals.keys())
    if n_env != len(sorted_keys):
        raise LoopNotCanonical("env values must be single arrays (no nested pytrees)")
    if len(jaxpr.invars) != 2 + n_env:
        raise LoopNotCanonical(
            "collapse=2 body must take (i, j, env) with env a flat dict of "
            f"arrays; got {len(jaxpr.invars)} invars for {n_env} env leaves"
        )
    iter_i, iter_j = jaxpr.invars[0], jaxpr.invars[1]
    var_of_key = {k: jaxpr.invars[2 + pos] for pos, k in enumerate(sorted_keys)}
    key_of_var = {id(v): k for k, v in var_of_key.items()}

    aff = _AffineEnv(
        {iter_i: NestAffine((1, 0), 0), iter_j: NestAffine((0, 1), 0)},
        const=lambda v: NestAffine((0, 0), v))
    # key -> list of (starts-affine-tuple, prefix r) slice uses
    slice_uses: dict[str, list[tuple[tuple, int]]] = {k: [] for k in env_keys}
    whole_use: dict[str, bool] = {k: False for k in env_keys}

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        for pos, iv in enumerate(eqn.invars):
            key = key_of_var.get(id(iv))
            if key is None:
                continue
            if prim == "dynamic_slice" and pos == 0:
                idx_atoms = eqn.invars[1:]
                sizes = eqn.params["slice_sizes"]
                shape = env_avals[key].shape
                starts = tuple(aff.lookup(at) for at in idx_atoms)
                r = _access_prefix(starts, tuple(sizes), tuple(shape))
                if r:
                    slice_uses[key].append((starts[:r], r))
                else:
                    whole_use[key] = True
            else:
                whole_use[key] = True
        aff.process(eqn)

    # --- write classification ---------------------------------------------
    flat_shapes, out_tree = jax.tree_util.tree_flatten(out_shape)
    positions = jax.tree_util.tree_unflatten(out_tree, list(range(len(flat_shapes))))
    outvars = jaxpr.outvars
    if not isinstance(positions, dict):
        raise LoopNotCanonical("body must return a dict of omp updates")

    def _out_affine(pos: int):
        atom = outvars[pos]
        if isinstance(atom, jcore.Literal):
            v = _literal_int(atom.val)
            return None if v is None else NestAffine((0, 0), v)
        return aff.lookup(atom)

    writes: dict[str, WriteInfo] = {}
    for key, upd in positions.items():
        if isinstance(upd, pragma.At):
            idx = upd.idx if isinstance(upd.idx, tuple) else (upd.idx,)
            if len(idx) != 2:
                raise LoopNotCanonical(
                    f"{key!r}: a collapse=2 write needs omp.at((i, j), v) "
                    f"with a 2-tuple index, got {len(idx)} indices"
                )
            affines2 = tuple(_out_affine(p) for p in idx)
            vshape = flat_shapes[upd.value]
            writes[key] = WriteInfo(
                WriteKind.AT,
                affines2=affines2,
                value_shape=tuple(vshape.shape),
                value_dtype=vshape.dtype,
            )
        elif isinstance(upd, pragma.Put):
            raise LoopNotCanonical(
                f"{key!r}: omp.put is not supported inside a collapse=2 "
                "nest (paper §3.1.3: the block is kept as OpenMP)"
            )
        elif isinstance(upd, pragma.Red):
            if key not in program.reduction:
                raise LoopNotCanonical(
                    f"omp.red() for {key!r} without a reduction clause "
                    "(paper: reductions must be declared with reduction(op: var))"
                )
            vshape = flat_shapes[upd.value]
            writes[key] = WriteInfo(
                WriteKind.RED,
                value_shape=tuple(vshape.shape),
                value_dtype=vshape.dtype,
                reduction_op=program.reduction[key],
            )
        else:
            raise LoopNotCanonical(
                f"update for {key!r} must be omp.at/omp.red in a collapse=2 "
                f"nest, got {type(upd).__name__}"
            )

    for key in program.reduction:
        if key in writes and writes[key].kind != WriteKind.RED:
            raise LoopNotCanonical(
                f"{key!r} is declared as a reduction but written with "
                f"{writes[key].kind.value}"
            )

    # --- assemble per-variable classification ------------------------------
    infos: dict[str, VarInfo] = {}
    all_keys = list(env_keys) + [k for k in writes if k not in env_avals]
    for key in all_keys:
        if key in env_avals:
            shape, dtype = env_avals[key].shape, env_avals[key].dtype
        else:
            w = writes[key]
            shape, dtype = w.value_shape, w.value_dtype
        if key in env_avals and whole_use[key]:
            read = ReadInfo(ReadKind.WHOLE)
        elif key in env_avals and slice_uses[key]:
            uses = slice_uses[key]
            r = min(u[1] for u in uses)
            maps: list[tuple] = []
            seen: set = set()
            degenerate = False
            for starts, _ in uses:
                t = starts[:r]
                # axes beyond the shared prefix must be serveable from a
                # window sharded on the prefix only: const indices
                if any(a is None or not a.is_const for a in starts[r:]):
                    degenerate = True
                    break
                sig = tuple((a.coeffs, a.b) for a in t)
                if sig not in seen:
                    seen.add(sig)
                    maps.append(t)
            if degenerate:
                read = ReadInfo(ReadKind.WHOLE)
            else:
                kind = ReadKind.SLICED if len(maps) == 1 else ReadKind.STENCIL
                read = ReadInfo(kind, slice_ndim=r, accesses=tuple(maps))
        else:
            read = ReadInfo(ReadKind.NONE)

        write = writes.get(key, WriteInfo(WriteKind.NONE))
        if write.kind == WriteKind.RED:
            klass = VarClass.REDUCTION
        elif write.kind == WriteKind.NONE:
            klass = VarClass.IN if read.kind != ReadKind.NONE else VarClass.UNUSED
        elif read.kind == ReadKind.NONE:
            klass = VarClass.OUT
        else:
            klass = VarClass.INOUT
        infos[key] = VarInfo(
            name=key, read=read, write=write, klass=klass,
            shape=tuple(shape), dtype=dtype,
        )

    return ContextInfo(vars=infos, env_keys=env_keys, update_keys=list(writes))
