"""Differential test harness: random canonical OMP programs vs their
transformations, across 1/2/4-device meshes.

This is the regression net under the communication-planner refactor:
programs are drawn from the canonical-form families the paper recognises
(identity / aligned / strided affine writes, stencil reads with halo
offsets, reductions, ``put``, serial glue, multi-loop chains; schedules
``static``/``dynamic``/``guided`` with and without explicit chunk sizes,
including zero-trip and trip_count < num_devices draws) and every
lowering must reproduce the shared-memory reference
(:func:`repro.core.transform.run_reference`).  Every variant routes
through the one entry point ``omp.compile``:

* ``Lowering.COLLECTIVE``, with ``shard`` = replicate and slice, plus a
  schedule-override draw that forces exactly one chunk per device (the
  static fast path: no ``lax.scan``, no dynamic window gather),
* ``Lowering.MASTER_WORKER`` (the paper's staging; needs >= 2 ranks),
* ``Lowering.FUSED`` regions, both ``comm="auto"`` (cost-modeled halo
  ``ppermute`` boundaries) and ``comm="gather"`` (the PR 1 baseline),
  each under ``comm_schedule`` = ``aggregate`` (packed payloads, fused
  reductions, prefetched exchanges) *and* ``inline`` — the two schedule
  modes must be bit-identical — plus the per-loop
  ``Lowering.COLLECTIVE`` staged fallback.

Single-device examples run in-process through hypothesis ``given``
(seed 0 always among them); the 2/4-device sweep runs in one subprocess with forced
virtual devices (``conftest.run_multidevice``) and re-draws the same
seeded cases there.
"""
import os
import random

import numpy as np

from hypothesis import example, given, settings, strategies as st

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FAMILIES = (
    "map", "stencil", "strided", "reduce", "put", "combo",
    "chain", "pingpong", "glue", "zerotrip",
)


def _schedule(rng):
    from repro import omp

    kind = rng.choice([omp.static, omp.dynamic, omp.guided])
    chunk = rng.choice([None, None, 1, 2, 3, 5])
    return kind(chunk)


def make_case(seed: int, family: str | None = None):
    """Build one random canonical program (or region) + env from a seed.

    Deterministic: the in-process and subprocess sweeps rebuild
    identical cases from the same seed.  ``family`` forces one program
    family (the multi-device sweep uses it to guarantee every family —
    in particular the halo-exercising stencil/pingpong ones — runs on
    every mesh size).
    """
    import jax.numpy as jnp

    from repro import omp

    rng = random.Random(seed)
    if family is None:
        family = rng.choice(FAMILIES)
    assert family in FAMILIES, family
    sched = _schedule(rng)
    fx = jnp.float32

    if family == "map":
        n = rng.randint(3, 24)
        start = rng.choice([0, 0, 1, 2])
        stop = rng.randint(start, n)          # may draw a zero-trip loop
        step = rng.choice([1, 1, 2])

        @omp.parallel_for(start=start, stop=stop, step=step, schedule=sched,
                          name=f"map{seed}")
        def prog(i, env):
            return {"y": omp.at(i, env["x"][i] * 2.0 + 1.0)}

        env = {"x": jnp.arange(n, dtype=fx) * 0.25, "y": -jnp.ones(n, fx)}

    elif family == "stencil":
        n = rng.randint(8, 24)
        w = rng.choice([1, 2])

        @omp.parallel_for(start=w, stop=n - w, schedule=sched,
                          name=f"stencil{seed}")
        def prog(i, env):
            v = (env["x"][i - w] + env["x"][i] + env["x"][i + w]) / 3.0
            return {"y": omp.at(i, v)}

        env = {"x": jnp.arange(n, dtype=fx) * 0.5, "y": -jnp.ones(n, fx)}

    elif family == "strided":
        t = rng.randint(1, 9)
        a = rng.choice([2, 3])
        b = rng.randint(0, 2)
        m = a * (t - 1) + b + 1

        @omp.parallel_for(stop=t, schedule=sched, name=f"strided{seed}")
        def prog(i, env):
            return {"z": omp.at(a * i + b, env["x"][i] + 3.0)}

        env = {"x": jnp.arange(max(t, 2), dtype=fx), "z": -jnp.ones(m, fx)}

    elif family == "reduce":
        n = rng.randint(1, 20)
        op = rng.choice(["+", "max", "min", "*"])
        fresh = rng.random() < 0.4

        @omp.parallel_for(stop=n, schedule=sched, reduction={"s": op},
                          name=f"reduce{seed}")
        def prog(i, env):
            return {"s": omp.red(env["x"][i])}

        # keep values near 1 so "*" stays well-conditioned
        env = {"x": 1.0 + 0.1 * jnp.sin(jnp.arange(n, dtype=fx))}
        if not fresh:
            env["s"] = fx(0.5)

    elif family == "put":
        t = rng.randint(1, 9)

        @omp.parallel_for(stop=t, schedule=sched, name=f"put{seed}")
        def prog(i, env):
            return {"w": omp.put(jnp.full((3,), 1.0, fx) * i)}

        env = {"x": jnp.arange(t, dtype=fx), "w": jnp.zeros(3, fx)}

    elif family == "combo":
        n = rng.randint(2, 16)

        @omp.parallel_for(stop=n, schedule=sched, reduction={"s": "+"},
                          name=f"combo{seed}")
        def prog(i, env):
            v = env["x"][i] * env["x"][i]
            return {"y": omp.at(i, v), "s": omp.red(v)}

        env = {"x": jnp.arange(n, dtype=fx) * 0.3, "y": jnp.zeros(n, fx),
               "s": fx(1.0)}

    elif family == "chain":
        n = rng.randint(4, 24)

        @omp.parallel_for(stop=n, schedule=sched, name=f"c1_{seed}")
        def l1(i, env):
            return {"tmp": omp.at(i, env["x"][i] * 2.0)}

        @omp.parallel_for(stop=n, schedule=sched, name=f"c2_{seed}")
        def l2(i, env):
            return {"y": omp.at(i, env["tmp"][i] + 1.0)}

        @omp.parallel_for(stop=n, schedule=sched, reduction={"tot": "+"},
                          name=f"c3_{seed}")
        def l3(i, env):
            return {"tot": omp.red(env["y"][i])}

        prog = omp.region(l1, l2, l3, name=f"chain{seed}")
        env = {"x": jnp.arange(n, dtype=fx) * 0.1, "tmp": jnp.zeros(n, fx),
               "y": jnp.zeros(n, fx), "tot": fx(0.0)}

    elif family == "pingpong":
        n = rng.randint(10, 28)

        def sweep(src, dst, name):
            @omp.parallel_for(start=1, stop=n - 1, schedule=sched, name=name)
            def body(i, env):
                v = 0.25 * (env[src][i - 1] + 2.0 * env[src][i]
                            + env[src][i + 1])
                return {dst: omp.at(i, v)}
            return body

        prog = omp.region(sweep("a", "b", f"s1_{seed}"),
                          sweep("b", "a", f"s2_{seed}"),
                          sweep("a", "b", f"s3_{seed}"),
                          name=f"pingpong{seed}")
        env = {"a": jnp.sin(jnp.arange(n, dtype=fx)),
               "b": jnp.zeros(n, fx)}

    elif family == "glue":
        n = rng.randint(4, 20)

        @omp.parallel_for(stop=n, schedule=sched, name=f"g1_{seed}")
        def g1(i, env):
            return {"tmp": omp.at(i, env["x"][i] * env["x"][i])}

        glue = omp.serial(lambda env: {"bias": env["bias"] * 0.5},
                          reads=("bias",), name=f"halve{seed}")

        @omp.parallel_for(stop=n, schedule=sched, name=f"g2_{seed}")
        def g2(i, env):
            return {"y": omp.at(i, env["tmp"][i] + env["bias"][0])}

        prog = omp.region(g1, glue, g2, name=f"glue{seed}")
        env = {"x": jnp.arange(n, dtype=fx) * 0.2, "tmp": jnp.zeros(n, fx),
               "y": jnp.zeros(n, fx), "bias": jnp.full((1,), 3.0, fx)}

    else:  # zerotrip
        n = rng.randint(3, 12)

        @omp.parallel_for(stop=0, schedule=sched, reduction={"s": "+"},
                          name=f"z0_{seed}")
        def z0(i, env):
            return {"y": omp.at(i, env["x"][i]), "s": omp.red(env["x"][i])}

        @omp.parallel_for(stop=n, schedule=sched, name=f"z1_{seed}")
        def z1(i, env):
            return {"y": omp.at(i, env["x"][i] + env["s"])}

        prog = omp.region(z0, z1, name=f"zerotrip{seed}")
        env = {"x": jnp.arange(n, dtype=fx), "y": jnp.zeros(n, fx),
               "s": fx(7.0)}

    return prog, env, family


def check_case(seed: int, mesh, family: str | None = None) -> str:
    """Every lowering of the drawn program must match the reference.

    Everything routes through ``omp.compile`` — the single entry point
    must handle every family × schedule × lowering × comm mode the
    legacy entry points covered (those survive only as shims; their
    equivalence is pinned in tests/test_api.py).
    """
    from repro import omp

    prog, env, family = make_case(seed, family)
    is_region = isinstance(prog, omp.ParallelRegion)
    ref = prog(env)
    p = mesh.shape["data"]

    variants = {}
    if is_region:
        variants["region_auto"] = omp.compile(prog, mesh, comm="auto")
        variants["region_inline"] = omp.compile(
            prog, mesh, comm="auto", comm_schedule="inline")
        variants["region_gather"] = omp.compile(prog, mesh, comm="gather")
        variants["region_staged"] = omp.compile(prog, mesh,
                                                lowering="collective")
        if p >= 2:
            variants["region_mw"] = omp.compile(
                prog, mesh, lowering="master_worker")
    else:
        variants["mpi"] = omp.compile(prog, mesh, lowering="collective")
        variants["mpi_sharded"] = omp.compile(
            prog, mesh, lowering="collective", shard="slice")
        t = len(range(prog.start, prog.stop, prog.step))
        if t > 0:
            # pin the one-chunk-per-device fast path (static slab body,
            # no scan): chunk = ceil(t / P) makes local_chunks == 1
            variants["mpi_onechunk"] = omp.compile(
                prog, mesh, lowering="collective", shard="slice",
                schedule=omp.static(-(-t // p)))
        if p >= 2:
            variants["mpi_mw"] = omp.compile(prog, mesh,
                                             lowering="master_worker")

    outs = {}
    for vname, dist in variants.items():
        got = dist(env)
        outs[vname] = got
        assert set(got) == set(ref), (
            f"seed={seed} {family}/{vname} P={p}: key set "
            f"{sorted(got)} != {sorted(ref)}")
        for k in ref:
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(ref[k]),
                rtol=1e-4, atol=1e-4,
                err_msg=f"seed={seed} {family}/{vname} P={p} key={k!r}")
    if "mpi_onechunk" in variants:
        assert variants["mpi_onechunk"].plan.chunks.local_chunks == 1
    if "region_inline" in outs:
        # the two schedule modes move identical bytes and must produce
        # bit-identical outputs
        for k in ref:
            np.testing.assert_array_equal(
                np.asarray(outs["region_auto"][k]),
                np.asarray(outs["region_inline"][k]),
                err_msg=f"seed={seed} {family} P={p} key={k!r}: "
                        "aggregate vs inline schedule diverged")
    return family


def run_sweep(seeds, device_counts) -> None:
    """Subprocess entry point: sweep seeds over real sub-meshes.

    Every family is forced once per mesh size (random seeds alone can
    miss the halo-exercising stencil/pingpong families), then the free
    seeds add schedule/shape variety on top.
    """
    import jax
    from jax.sharding import Mesh

    covered = set()
    for k in device_counts:
        mesh = Mesh(np.asarray(jax.devices()[:k]), ("data",))
        for j, fam in enumerate(FAMILIES):
            covered.add(check_case(1000 * k + j, mesh, family=fam))
        for seed in seeds:
            covered.add(check_case(seed, mesh))
    assert covered == set(FAMILIES), sorted(set(FAMILIES) - covered)
    print("families:", ",".join(sorted(covered)))


# ---------------------------------------------------------------------------
# Rank-2 (collapse=2) program families over 2-D meshes
# ---------------------------------------------------------------------------

FAMILIES2 = ("heat2d", "transpose2", "rowreduce2", "matmul2")


def make_case2(seed: int, family: str | None = None):
    """Build one random canonical ``collapse=2`` program (or region) +
    env from a seed: the 2-D families of the paper's benchmark suite
    (Jacobi/heat stencils, transposed feeds, reductions, matmul tiles).
    """
    import jax.numpy as jnp

    from repro import omp

    rng = random.Random(seed)
    if family is None:
        family = rng.choice(FAMILIES2)
    assert family in FAMILIES2, family
    sched = _schedule(rng)
    fx = jnp.float32

    if family == "heat2d":
        n = rng.randint(6, 14)
        m = rng.randint(6, 14)

        def sweep(src, dst, name):
            @omp.parallel_for(start=(1, 1), stop=(n - 1, m - 1), collapse=2,
                              schedule=sched, name=name)
            def body(i, j, env):
                v = 0.25 * (env[src][i - 1, j] + env[src][i + 1, j]
                            + env[src][i, j - 1] + env[src][i, j + 1])
                return {dst: omp.at((i, j), v)}
            return body

        prog = omp.region(sweep("a", "b", f"h1_{seed}"),
                          sweep("b", "a", f"h2_{seed}"),
                          name=f"heat2d{seed}")
        env = {"a": jnp.sin(jnp.arange(n * m, dtype=fx)).reshape(n, m),
               "b": jnp.zeros((n, m), fx)}

    elif family == "transpose2":
        n = rng.randint(4, 10)

        @omp.parallel_for(stop=(n, n), collapse=2, schedule=sched,
                          name=f"t1_{seed}")
        def t1(i, j, env):
            return {"t": omp.at((i, j), env["x"][i, j] * 2.0)}

        @omp.parallel_for(stop=(n, n), collapse=2, schedule=sched,
                          name=f"t2_{seed}")
        def t2(i, j, env):
            return {"y": omp.at((i, j), env["t"][j, i] + 1.0)}

        prog = omp.region(t1, t2, name=f"transpose2_{seed}")
        env = {"x": jnp.arange(n * n, dtype=fx).reshape(n, n) * 0.1,
               "t": jnp.zeros((n, n), fx), "y": jnp.zeros((n, n), fx)}

    elif family == "rowreduce2":
        n = rng.randint(3, 10)
        m = rng.randint(3, 10)
        op = rng.choice(["+", "max", "min", "*"])
        fresh = rng.random() < 0.4

        @omp.parallel_for(stop=(n, m), collapse=2, schedule=sched,
                          reduction={"s": op}, name=f"rr_{seed}")
        def prog(i, j, env):
            return {"s": omp.red(env["x"][i, j])}

        env = {"x": 1.0 + 0.1 * jnp.sin(
            jnp.arange(n * m, dtype=fx)).reshape(n, m)}
        if not fresh:
            env["s"] = fx(0.5)

    else:  # matmul2
        n = rng.randint(3, 9)
        m = rng.randint(3, 9)
        kk = rng.randint(2, 6)

        @omp.parallel_for(stop=(n, m), collapse=2, schedule=sched,
                          name=f"mm_{seed}")
        def prog(i, j, env):
            return {"C": omp.at((i, j),
                                jnp.dot(env["A"][i], env["B"][:, j]))}

        env = {"A": jnp.arange(n * kk, dtype=fx).reshape(n, kk) * 0.05,
               "B": jnp.arange(kk * m, dtype=fx).reshape(kk, m) * 0.03,
               "C": -jnp.ones((n, m), fx)}

    return prog, env, family


def check_case2(seed: int, mesh, family: str | None = None) -> str:
    """Every rank-2 lowering of the drawn program must match the
    shared-memory reference on the given 2-D mesh."""
    from repro import omp

    prog, env, family = make_case2(seed, family)
    is_region = isinstance(prog, omp.ParallelRegion)
    ref = prog(env)
    shape = (mesh.shape["i"], mesh.shape["j"])

    variants = {}
    if is_region:
        variants["region2_auto"] = omp.compile(prog, mesh, comm="auto")
        variants["region2_inline"] = omp.compile(
            prog, mesh, comm="auto", comm_schedule="inline")
        variants["region2_gather"] = omp.compile(prog, mesh, comm="gather")
    else:
        variants["mpi2"] = omp.compile(prog, mesh, lowering="collective")
        variants["mpi2_sharded"] = omp.compile(
            prog, mesh, lowering="collective", shard="slice")
        variants["region2_auto"] = omp.compile(
            omp.ParallelRegion((prog,)), mesh)

    outs = {}
    for vname, dist in variants.items():
        got = dist(env)
        outs[vname] = got
        assert set(got) == set(ref), (
            f"seed={seed} {family}/{vname} mesh={shape}: key set "
            f"{sorted(got)} != {sorted(ref)}")
        for k in ref:
            np.testing.assert_allclose(
                np.asarray(got[k]), np.asarray(ref[k]),
                rtol=1e-4, atol=1e-4,
                err_msg=f"seed={seed} {family}/{vname} mesh={shape} key={k!r}")
    if "region2_inline" in outs:
        for k in ref:
            np.testing.assert_array_equal(
                np.asarray(outs["region2_auto"][k]),
                np.asarray(outs["region2_inline"][k]),
                err_msg=f"seed={seed} {family} mesh={shape} key={k!r}: "
                        "aggregate vs inline schedule diverged")
    return family


def run_sweep2(mesh_shapes) -> None:
    """Subprocess entry point: every 2-D family on every mesh shape."""
    from repro.compat import make_mesh

    covered = set()
    for si, shape in enumerate(mesh_shapes):
        mesh = make_mesh(shape, ("i", "j"))
        for fj, fam in enumerate(FAMILIES2):
            covered.add(check_case2(7000 + 100 * si + fj, mesh, family=fam))
    assert covered == set(FAMILIES2), sorted(set(FAMILIES2) - covered)
    print("families2:", ",".join(sorted(covered)))


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
@example(seed=0)
def test_differential_2d_single_device(seed):
    """1x1 meshes in-process: the rank-2 transformation must be a
    semantic no-op for every drawn collapse=2 program."""
    from repro.compat import make_mesh

    mesh = make_mesh((1, 1), ("i", "j"))
    check_case2(seed, mesh)


def test_differential_2d_multidevice(multidevice):
    """2x1 / 2x2 / 4x2 meshes (8 virtual devices, one subprocess): every
    rank-2 lowering of every family matches the reference."""
    out = multidevice(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        from tests.test_differential import run_sweep2
        run_sweep2(((2, 1), (2, 2), (4, 2)))
        print("OKDIFF2")
    """, n_devices=8)
    assert "OKDIFF2" in out
    families_line = [l for l in out.splitlines()
                     if l.startswith("families2:")][0]
    for fam in FAMILIES2:
        assert fam in families_line, fam


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
@example(seed=0)
def test_differential_single_device(seed):
    """1-device meshes: the transformation must be a semantic no-op for
    every drawn program."""
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    check_case(seed, mesh)


def test_differential_multidevice(multidevice):
    """2- and 4-device meshes (4 virtual devices, one subprocess):
    every lowering of every drawn case matches the reference."""
    out = multidevice(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        from tests.test_differential import FAMILIES, run_sweep
        run_sweep(seeds=range(4), device_counts=(2, 4))
        print("OKDIFF")
    """, n_devices=4)
    assert "OKDIFF" in out
    families_line = [l for l in out.splitlines()
                     if l.startswith("families:")][0]
    for fam in FAMILIES:
        assert fam in families_line, fam


# ---------------------------------------------------------------------------
# Lowering.PALLAS: the tiled shard-local kernel backend must match BOTH
# the shared-memory reference and the lax lowering of the same draw
# ---------------------------------------------------------------------------


def check_case_pallas(seed: int, mesh, family: str | None = None) -> str:
    """Differential wall for the Pallas backend (interpret on CPU):
    same drawn program, three executions — shared-memory reference, the
    lax lowering (collective / fused region), and ``lowering=pallas`` —
    and the pallas output must match both."""
    from repro import omp

    prog, env, family = make_case(seed, family)
    is_region = isinstance(prog, omp.ParallelRegion)
    ref = prog(env)
    p = mesh.shape["data"]
    if is_region:
        lax_c = omp.compile(prog, mesh, comm="auto")
    else:
        lax_c = omp.compile(prog, mesh, lowering="collective")
    pal_c = omp.compile(prog, mesh, lowering="pallas")
    lax_out = lax_c(env)
    pal_out = pal_c(env)
    assert set(pal_out) == set(ref), (
        f"seed={seed} {family}/pallas P={p}: key set "
        f"{sorted(pal_out)} != {sorted(ref)}")
    for k in ref:
        np.testing.assert_allclose(
            np.asarray(pal_out[k]), np.asarray(ref[k]),
            rtol=1e-4, atol=1e-4,
            err_msg=f"seed={seed} {family}/pallas-vs-ref P={p} key={k!r}")
        np.testing.assert_allclose(
            np.asarray(pal_out[k]), np.asarray(lax_out[k]),
            rtol=1e-4, atol=1e-4,
            err_msg=f"seed={seed} {family}/pallas-vs-lax P={p} key={k!r}")
    return family


def check_case2_pallas(seed: int, mesh, family: str | None = None) -> str:
    """Rank-2 pallas differential: collapse=2 families on 2-D meshes."""
    from repro import omp

    prog, env, family = make_case2(seed, family)
    is_region = isinstance(prog, omp.ParallelRegion)
    ref = prog(env)
    shape = (mesh.shape["i"], mesh.shape["j"])
    if is_region:
        lax_c = omp.compile(prog, mesh, comm="auto")
    else:
        lax_c = omp.compile(prog, mesh, lowering="collective")
    pal_c = omp.compile(prog, mesh, lowering="pallas")
    lax_out = lax_c(env)
    pal_out = pal_c(env)
    assert set(pal_out) == set(ref), (
        f"seed={seed} {family}/pallas mesh={shape}: key set "
        f"{sorted(pal_out)} != {sorted(ref)}")
    for k in ref:
        np.testing.assert_allclose(
            np.asarray(pal_out[k]), np.asarray(ref[k]),
            rtol=1e-4, atol=1e-4,
            err_msg=f"seed={seed} {family}/pallas-vs-ref "
                    f"mesh={shape} key={k!r}")
        np.testing.assert_allclose(
            np.asarray(pal_out[k]), np.asarray(lax_out[k]),
            rtol=1e-4, atol=1e-4,
            err_msg=f"seed={seed} {family}/pallas-vs-lax "
                    f"mesh={shape} key={k!r}")
    return family


def test_differential_pallas_every_family():
    """Every rank-1 family through the pallas backend, in-process on a
    1-device mesh (interpret mode)."""
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    for j, fam in enumerate(FAMILIES):
        check_case_pallas(500 + j, mesh, family=fam)


def test_differential_pallas2_every_family():
    """Every rank-2 family through the pallas backend on a 1x1 mesh."""
    from repro.compat import make_mesh

    mesh = make_mesh((1, 1), ("i", "j"))
    for j, fam in enumerate(FAMILIES2):
        check_case2_pallas(600 + j, mesh, family=fam)


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
@example(seed=0)
def test_differential_pallas_single_device(seed):
    """Random draws through the pallas backend (any family)."""
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    check_case_pallas(seed, mesh)


def run_sweep_pallas() -> None:
    """Subprocess entry point: every family through the pallas backend
    on real multi-device meshes (rank-1 on 4 ranks, rank-2 on 2x2)."""
    import jax
    from jax.sharding import Mesh

    from repro.compat import make_mesh

    covered = set()
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
    for j, fam in enumerate(FAMILIES):
        covered.add(check_case_pallas(4000 + j, mesh, family=fam))
    mesh2 = make_mesh((2, 2), ("i", "j"))
    for j, fam in enumerate(FAMILIES2):
        covered.add(check_case2_pallas(4100 + j, mesh2, family=fam))
    assert covered == set(FAMILIES) | set(FAMILIES2), sorted(covered)
    print("families_pallas:", ",".join(sorted(covered)))


def test_differential_pallas_multidevice(multidevice):
    """Pallas backend on real multi-device meshes (8 virtual devices,
    one subprocess): every family, both ranks, vs reference AND lax."""
    out = multidevice(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        from tests.test_differential import run_sweep_pallas
        run_sweep_pallas()
        print("OKPALLAS")
    """, n_devices=8)
    assert "OKPALLAS" in out
    families_line = [l for l in out.splitlines()
                     if l.startswith("families_pallas:")][0]
    for fam in FAMILIES + FAMILIES2:
        assert fam in families_line, fam
