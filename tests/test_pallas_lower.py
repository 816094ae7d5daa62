"""Unit + property tests for the Pallas backend's tile derivation,
span planning, and KernelPlan reporting (``src/repro/core/pallas_lower.py``).

Correctness of the kernels themselves is pinned by the differential
wall in tests/test_differential.py (``check_case_pallas`` /
``check_case2_pallas``); this file pins the *geometry*: tile shapes,
slab coverage (property-based — no overlap, no gap, masked remainder
lanes only), fusion span boundaries, and the rendered report.
"""
import os

import numpy as np

from hypothesis import given, settings, strategies as st

os.environ.setdefault("JAX_PLATFORMS", "cpu")


# ---------------------------------------------------------------------------
# Tile derivation units
# ---------------------------------------------------------------------------


def test_derive_axis_tiles_small_chunk_pads_to_sublane():
    import jax.numpy as jnp

    from repro.core.nest import derive_axis_tiles

    tl = derive_axis_tiles(1, jnp.float32)
    assert (tl.chunk, tl.tile, tl.n_tiles, tl.padded) == (1, 8, 1, 8)
    assert tl.masked_lanes == 7


def test_derive_axis_tiles_rounds_up_to_sublane():
    import jax.numpy as jnp

    from repro.core.nest import derive_axis_tiles

    tl = derive_axis_tiles(17, jnp.float32)
    assert (tl.tile, tl.n_tiles, tl.padded) == (24, 1, 24)
    assert tl.masked_lanes == 7


def test_derive_axis_tiles_caps_tile_and_splits():
    import jax.numpy as jnp

    from repro.core.nest import derive_axis_tiles

    tl = derive_axis_tiles(300, jnp.float32)
    assert (tl.tile, tl.n_tiles, tl.padded) == (256, 2, 512)
    assert tl.masked_lanes == 212


def test_derive_axis_tiles_dtype_sublane():
    import jax.numpy as jnp

    from repro.core.nest import derive_axis_tiles, sublane_for

    assert sublane_for(jnp.float32) == 8
    assert sublane_for(jnp.bfloat16) == 16
    assert sublane_for(jnp.int8) == 32
    tl = derive_axis_tiles(20, jnp.bfloat16)
    assert tl.tile == 32 and tl.n_tiles == 1 and tl.masked_lanes == 12


# ---------------------------------------------------------------------------
# Property wall: tile geometry must cover the slab exactly —
# no overlap, no gap, masked remainder lanes only (satellite 2)
# ---------------------------------------------------------------------------


@settings(max_examples=60)
@given(chunk=st.integers(1, 700),
       dtype_name=st.sampled_from(["float32", "float64", "bfloat16",
                                   "int8", "int32"]))
def test_axis_tiles_cover_partitions_chunk(chunk, dtype_name):
    import jax.numpy as jnp

    from repro.core.nest import derive_axis_tiles, sublane_for

    dt = getattr(jnp, dtype_name)
    tl = derive_axis_tiles(chunk, dt)
    assert tl.tile % sublane_for(dt) == 0
    assert tl.padded == tl.n_tiles * tl.tile >= chunk
    assert 0 <= tl.masked_lanes < tl.tile
    seen = np.zeros(chunk, dtype=int)
    for start, valid in tl.cover():
        assert valid >= 1                    # no empty tiles
        seen[start:start + valid] += 1
    assert (seen == 1).all()                 # exact partition of [0, chunk)


@settings(max_examples=40)
@given(n=st.integers(0, 200), num_devices=st.sampled_from([1, 2, 3, 4, 8]),
       chunk_req=st.one_of(st.none(), st.integers(1, 16)),
       halo=st.integers(0, 3))
def test_chunk_plan_plus_tiles_cover_every_iteration(n, num_devices,
                                                     chunk_req, halo):
    """Composed coverage: chunk-cyclic dealing x tile cover must visit
    every global iteration exactly once; halo never shifts lane
    ownership (it only widens the read window)."""
    import jax.numpy as jnp

    from repro.core import pragma
    from repro.core.loop import analyze_loop
    from repro.core.nest import derive_axis_tiles
    from repro.core.schedule import make_chunk_plan

    loop = analyze_loop(0, n, 1)
    ch = make_chunk_plan(loop, pragma.static(chunk_req), num_devices)
    tl = derive_axis_tiles(ch.chunk, jnp.float32)
    seen = np.zeros(n, dtype=int)
    for d in range(ch.num_devices):
        for q in range(ch.local_chunks):
            j = q * ch.num_devices + d
            k0 = j * ch.chunk
            for start, valid in tl.cover():
                for lane in range(start, start + valid):
                    k = k0 + lane
                    if k < n:
                        seen[k] += 1
    assert (seen == 1).all()


@settings(max_examples=25)
@given(n_i=st.integers(1, 40), n_j=st.integers(1, 40),
       p_i=st.sampled_from([1, 2, 4]), p_j=st.sampled_from([1, 2]),
       c_i=st.one_of(st.none(), st.integers(1, 7)),
       c_j=st.one_of(st.none(), st.integers(1, 7)))
def test_chunk_plan_plus_tiles_cover_2d(n_i, n_j, p_i, p_j, c_i, c_j):
    """Rank-2: the cross product of two per-axis covers partitions the
    collapse(2) iteration space exactly."""
    import jax.numpy as jnp

    from repro.core import pragma
    from repro.core.loop import analyze_loop
    from repro.core.nest import derive_axis_tiles
    from repro.core.schedule import make_chunk_plan

    covers = []
    for n, p, c in ((n_i, p_i, c_i), (n_j, p_j, c_j)):
        ch = make_chunk_plan(analyze_loop(0, n, 1), pragma.static(c), p)
        tl = derive_axis_tiles(ch.chunk, jnp.float32)
        ks = []
        for d in range(ch.num_devices):
            for q in range(ch.local_chunks):
                k0 = (q * ch.num_devices + d) * ch.chunk
                for start, valid in tl.cover():
                    ks.extend(k0 + lane
                              for lane in range(start, start + valid)
                              if k0 + lane < n)
        covers.append(ks)
    seen = np.zeros((n_i, n_j), dtype=int)
    for ki in covers[0]:
        for kj in covers[1]:
            seen[ki, kj] += 1
    assert (seen == 1).all()


# ---------------------------------------------------------------------------
# Span planning + KernelPlan artifact
# ---------------------------------------------------------------------------


def _mesh1(k=1):
    import jax
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:k]), ("data",))


def test_block_kernel_plan_single_span():
    from repro import omp

    @omp.parallel_for(stop=37, name="mapk")
    def prog(i, env):
        return {"y": omp.at(i, env["x"][i] * 2.0)}

    import jax.numpy as jnp

    env = {"x": jnp.arange(37, dtype=jnp.float32),
           "y": jnp.zeros(37, jnp.float32)}
    c = omp.compile(prog, _mesh1(), lowering="pallas", env_like=env)
    kp = c.kernel_plan
    assert isinstance(kp, omp.KernelPlan)
    assert kp.n_kernels == 1 and kp.n_loop_stages == 1
    assert kp.spans[0].stage_names == ("mapk",)
    assert kp.spans[0].rank == 1
    assert [p.name for p in c.passes].count("pallas") == 1


def test_kernel_plan_absent_without_pallas():
    from repro import omp

    @omp.parallel_for(stop=8, name="mapl")
    def prog(i, env):
        return {"y": omp.at(i, env["x"][i])}

    import jax.numpy as jnp

    env = {"x": jnp.arange(8, dtype=jnp.float32),
           "y": jnp.zeros(8, jnp.float32)}
    c = omp.compile(prog, _mesh1(), lowering="collective", env_like=env)
    assert c.kernel_plan is None
    assert "pallas" not in [p.name for p in c.passes]


def _chain_region(omp, jnp, n=21):
    @omp.parallel_for(stop=n, name="k1")
    def l1(i, env):
        return {"tmp": omp.at(i, env["x"][i] * 2.0)}

    @omp.parallel_for(stop=n, name="k2")
    def l2(i, env):
        return {"y": omp.at(i, env["tmp"][i] + 1.0)}

    @omp.parallel_for(stop=n, name="k3", reduction={"tot": "+"})
    def l3(i, env):
        return {"tot": omp.red(env["y"][i])}

    prog = omp.region(l1, l2, l3, name="chaink")
    env = {"x": jnp.arange(n, dtype=jnp.float32) * 0.1,
           "tmp": jnp.zeros(n, jnp.float32),
           "y": jnp.zeros(n, jnp.float32), "tot": jnp.float32(0.0)}
    return prog, env


def test_region_chain_fuses_into_one_span():
    """Resident hand-offs with identical geometry fuse: the 3-stage
    chain becomes ONE kernel with VMEM-forwarded intermediates."""
    import jax.numpy as jnp

    from repro import omp

    prog, env = _chain_region(omp, jnp)
    c = omp.compile(prog, _mesh1(), lowering="pallas", env_like=env)
    kp = c.kernel_plan
    assert kp.n_kernels == 1 and kp.max_fused == 3
    assert kp.spans[0].stage_names == ("k1", "k2", "k3")
    assert set(kp.spans[0].forwarded) == {"tmp", "y"}


def test_region_halo_exchange_breaks_spans():
    """A halo feed means an exchange sits between stages — the
    ping-pong sweeps must NOT fuse."""
    import jax.numpy as jnp

    from repro import omp

    n = 18

    def sweep(src, dst, name):
        @omp.parallel_for(start=1, stop=n - 1, name=name)
        def body(i, env):
            v = (env[src][i - 1] + env[src][i] + env[src][i + 1]) / 3.0
            return {dst: omp.at(i, v)}
        return body

    prog = omp.region(sweep("a", "b", "p1"), sweep("b", "a", "p2"),
                      name="pingk")
    env = {"a": jnp.sin(jnp.arange(n, dtype=jnp.float32)),
           "b": jnp.zeros(n, jnp.float32)}
    c = omp.compile(prog, _mesh1(), lowering="pallas", env_like=env)
    assert c.kernel_plan.n_kernels == 2
    assert c.kernel_plan.max_fused == 1


def test_region_serial_glue_breaks_spans():
    import jax.numpy as jnp

    from repro import omp

    @omp.parallel_for(stop=9, name="s1")
    def g1(i, env):
        return {"tmp": omp.at(i, env["x"][i] * env["x"][i])}

    glue = omp.serial(lambda env: {"bias": env["bias"] * 0.5},
                      reads=("bias",), name="halve")

    @omp.parallel_for(stop=9, name="s2")
    def g2(i, env):
        return {"y": omp.at(i, env["tmp"][i] + env["bias"][0])}

    prog = omp.region(g1, glue, g2, name="gluek")
    env = {"x": jnp.arange(9, dtype=jnp.float32),
           "tmp": jnp.zeros(9, jnp.float32),
           "y": jnp.zeros(9, jnp.float32),
           "bias": jnp.full((1,), 3.0, jnp.float32)}
    c = omp.compile(prog, _mesh1(), lowering="pallas", env_like=env)
    assert c.kernel_plan.n_kernels == 2
    assert all(len(s.stage_names) == 1 for s in c.kernel_plan.spans)


def test_kernel_plan_report_golden():
    """``Compiled.report()`` renders the tile geometry + fusion spans."""
    import jax.numpy as jnp

    from repro import omp

    prog, env = _chain_region(omp, jnp)
    c = omp.compile(prog, _mesh1(), lowering="pallas", env_like=env)
    rep = c.report()
    assert "pallas: exchange-free compute spans + chunk geometry" in rep
    assert "pallas kernels: 1 span(s) over 3 loop stage(s)" in rep
    assert "k1+k2+k3: grid=" in rep
    assert "vmem-forwarded: tmp, y" in rep
    # the one-span line carries the tile geometry verbatim
    span = c.kernel_plan.spans[0]
    assert span.describe() in rep


def test_resolve_interpret():
    from repro.core.pallas_lower import resolve_interpret

    mesh = _mesh1()
    assert resolve_interpret(None, mesh) is True      # CPU -> interpret
    assert resolve_interpret(True, mesh) is True
    assert resolve_interpret(False, mesh) is False


def test_pallas_smoke_matches_reference():
    """One end-to-end run (interpret) against the shared-memory
    reference — the full wall lives in tests/test_differential.py."""
    import jax.numpy as jnp

    from repro import omp

    prog, env = _chain_region(omp, jnp)
    ref = prog(env)
    got = omp.compile(prog, _mesh1(), lowering="pallas")(env)
    for k in ref:
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(ref[k]),
                                   rtol=1e-5, atol=1e-5)
