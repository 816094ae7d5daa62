"""The paper's central claim — "the generated code is correct by
construction" — validated as transform(program)(env) == program(env).

Single-device mesh runs exercise all codegen paths cheaply; hypothesis
generates random affine loop programs; a subprocess test covers real
8-device execution for both lowerings.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import omp
from repro.compat import make_mesh


def mesh1():
    return make_mesh((1,), ("data",))


def _close(a, b, tol=1e-5):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=tol, atol=tol)


def _check_all(program, env, schedules=("static", "dynamic", "guided")):
    ref = program(env)
    for kind in schedules:
        program.schedule = omp.Schedule(kind)
        out = omp.to_mpi(program, mesh1())(env)
        for k in ref:
            _close(out[k], ref[k])
    return ref


def test_identity_write():
    @omp.parallel_for(stop=23)
    def b(i, env):
        return {"y": omp.at(i, env["x"][i] * 2.0 + i)}

    _check_all(b, {"x": jnp.arange(23, dtype=jnp.float32),
                   "y": jnp.zeros(23)})


def test_shard_inputs_matches():
    @omp.parallel_for(stop=23)
    def b(i, env):
        return {"y": omp.at(i, env["x"][i] * 2.0)}

    env = {"x": jnp.arange(23, dtype=jnp.float32), "y": jnp.zeros(23)}
    ref = b(env)
    out = omp.to_mpi(b, mesh1(), shard_inputs=True)(env)
    _close(out["y"], ref["y"])


def test_strided_write_and_partial():
    @omp.parallel_for(stop=10)
    def b(i, env):
        return {"y": omp.at(3 * i + 2, env["x"][i])}

    env = {"x": jnp.arange(10, dtype=jnp.float32),
           "y": -jnp.ones(40, jnp.float32)}
    _check_all(b, env)

    @omp.parallel_for(stop=10)
    def b2(i, env):
        return {"y": omp.at(i + 4, env["x"][i])}

    _check_all(b2, {"x": jnp.arange(10, dtype=jnp.float32),
                    "y": -jnp.ones(20, jnp.float32)})


def test_put_last_iteration_wins():
    @omp.parallel_for(stop=9)
    def b(i, env):
        return {"z": omp.put(jnp.full((5,), i, jnp.float32))}

    ref = _check_all(b, {"z": jnp.zeros(5)})
    assert float(ref["z"][0]) == 8.0


def test_nonaffine_write_rejected():
    @omp.parallel_for(stop=8)
    def b(i, env):
        return {"y": omp.at(i * i, env["x"][i])}

    env = {"x": jnp.zeros(64), "y": jnp.zeros(64)}
    with pytest.raises(omp.LoopNotCanonical):
        omp.to_mpi(b, mesh1(), env_like=env)


def test_concurrent_write_rejected():
    @omp.parallel_for(stop=8)
    def b(i, env):
        return {"y": omp.at(0 * i, env["x"][i])}

    env = {"x": jnp.zeros(8), "y": jnp.zeros(8)}
    with pytest.raises(omp.LoopNotCanonical):
        omp.to_mpi(b, mesh1(), env_like=env)


def test_multiblock_pipeline_2mm_style():
    """Two chained blocks (2mm): the output of block 1 feeds block 2."""
    m, k, n = 12, 8, 10
    rng = np.random.default_rng(1)
    A = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    B = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    C = jnp.asarray(rng.normal(size=(n, k)).astype(np.float32))

    @omp.parallel_for(stop=m, name="mm1")
    def mm1(i, env):
        return {"tmp": omp.at(i, env["A"][i] @ env["B"])}

    @omp.parallel_for(stop=m, name="mm2")
    def mm2(i, env):
        return {"D": omp.at(i, env["tmp"][i] @ env["C"])}

    env = {"A": A, "B": B, "C": C,
           "tmp": jnp.zeros((m, n)), "D": jnp.zeros((m, k))}
    ref = mm2(mm1(env))
    d1 = omp.to_mpi(mm1, mesh1())
    d2 = omp.to_mpi(mm2, mesh1())
    out = d2(d1(env))
    _close(out["D"], ref["D"], tol=1e-4)


# ---------------------------------------------------------------------------
# Property-based: random affine programs
# ---------------------------------------------------------------------------


@settings(max_examples=25, deadline=None)
@given(
    t=st.integers(1, 40),
    a=st.integers(1, 3),
    b=st.integers(0, 5),
    chunk=st.one_of(st.none(), st.integers(1, 7)),
    kind=st.sampled_from(["static", "dynamic", "guided"]),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_affine_write(t, a, b, chunk, kind, seed):
    size = a * (t - 1) + b + 1
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=t).astype(np.float32))
    y = jnp.asarray(rng.normal(size=size).astype(np.float32))

    @omp.parallel_for(stop=t, schedule=omp.Schedule(kind, chunk))
    def prog(i, env):
        return {"y": omp.at(a * i + b, env["x"][i] * 3.0 - 1.0)}

    env = {"x": x, "y": y}
    ref = prog(env)
    out = omp.to_mpi(prog, mesh1())(env)
    _close(out["y"], ref["y"])


@settings(max_examples=15, deadline=None)
@given(
    t=st.integers(1, 30),
    op=st.sampled_from(["+", "max", "min", "*"]),
    seed=st.integers(0, 2**31 - 1),
)
def test_property_reductions(t, op, seed):
    rng = np.random.default_rng(seed)
    # keep '*' well-conditioned
    x = jnp.asarray((1.0 + 0.01 * rng.normal(size=t)).astype(np.float32))

    @omp.parallel_for(stop=t, reduction={"r": op})
    def prog(i, env):
        return {"r": omp.red(env["x"][i])}

    env = {"x": x, "r": jnp.float32(1.5)}
    ref = prog(env)
    out = omp.to_mpi(prog, mesh1())(env)
    _close(out["r"], ref["r"], tol=1e-4)


# ---------------------------------------------------------------------------
# Real multi-device execution (subprocess with 8 virtual devices)
# ---------------------------------------------------------------------------


def test_eight_device_both_lowerings(multidevice):
    out = multidevice("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import omp
        from repro.compat import make_mesh

        mesh = make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        N = 53
        x = jnp.asarray(rng.normal(size=N).astype(np.float32))

        @omp.parallel_for(stop=N, schedule=omp.dynamic(),
                          reduction={"tot": "+"})
        def prog(i, env):
            v = env["x"][i] * 2.0
            return {"y": omp.at(i, v), "tot": omp.red(v)}

        env = {"x": x, "y": jnp.zeros(N), "tot": jnp.float32(0)}
        ref = prog(env)
        for lowering in ("collective", "master_worker"):
            out = omp.to_mpi(prog, mesh, lowering=lowering)(env)
            for k in ref:
                assert np.allclose(out[k], ref[k], atol=1e-5), (lowering, k)
        print("OK8")
    """)
    assert "OK8" in out


def test_stencil_halo_sharded_inputs():
    """jacobi-style stencil with shard_inputs: the halo path must match
    the shared-memory reference (beyond-paper slice+halo transfer)."""
    n = 41
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=n).astype(np.float32))

    @omp.parallel_for(start=1, stop=n - 1)
    def jac(i, env):
        v = (env["x"][i - 1] + env["x"][i] + env["x"][i + 1]) / 3.0
        return {"y": omp.at(i, v)}

    env = {"x": x, "y": jnp.zeros(n, jnp.float32)}
    ref = jac(env)
    dist = omp.to_mpi(jac, mesh1(), shard_inputs=True)
    out = dist(env)
    assert dist.plan.vars["x"].in_strategy == "shard_halo"
    _close(out["y"], ref["y"])


def test_stencil_halo_eight_devices(multidevice):
    out = multidevice("""
        import jax, jax.numpy as jnp, numpy as np
        from repro import omp
        from repro.compat import make_mesh

        mesh = make_mesh((8,), ("data",))
        n = 67
        rng = np.random.default_rng(7)
        x = jnp.asarray(rng.normal(size=(n, 5)).astype(np.float32))

        @omp.parallel_for(start=2, stop=n - 2)
        def sten(i, env):
            v = (env["x"][i - 2] + env["x"][i] + env["x"][i + 2]) / 3.0
            return {"y": omp.at(i, v)}

        env = {"x": x, "y": jnp.zeros((n, 5), jnp.float32)}
        ref = sten(env)
        dist = omp.to_mpi(sten, mesh, shard_inputs=True)
        got = dist(env)
        assert dist.plan.vars["x"].in_strategy == "shard_halo", \
            dist.plan.vars["x"].in_strategy
        assert np.allclose(np.asarray(got["y"]), np.asarray(ref["y"]),
                           atol=1e-5)
        print("OKHALO")
    """)
    assert "OKHALO" in out
