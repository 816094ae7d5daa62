"""Straggler-weighted chunk schedules: slot layout, validation, and the
differential pin that weighted outputs match unweighted bit-for-bit.

A weighted schedule changes *who runs which chunk*, never *what is
computed*: `make_chunk_plan(weights=...)` re-deals chunk ownership via
`rebalance_chunks` and records the permutation in `ChunkPlan.slot_map`;
staging/reassembly gather through it.  Outputs outside reductions are
therefore bit-identical to the cyclic deal; reductions regroup their
per-device partial folds and match to float tolerance.
"""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro import omp
from repro.compat import make_mesh
from repro.core.loop import analyze_loop
from repro.core.schedule import make_chunk_plan


def _plan(trip_count, chunk, num_devices, weights=None):
    return make_chunk_plan(analyze_loop(0, trip_count, 1), omp.static(chunk),
                           num_devices, weights=weights)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------------------- plan layout --


def test_weighted_plan_slot_map_is_a_padded_permutation():
    ch = _plan(37, 3, 4, weights=[4.0, 1.0, 1.0, 1.0])
    k = ch.real_chunks
    assert k == 13
    real = [j for j in ch.slot_map if j < k]
    assert sorted(real) == list(range(k))          # every chunk exactly once
    assert all(j == k for j in ch.slot_map if j >= k)   # sentinel = k
    assert ch.num_chunks == ch.local_chunks * 4
    assert ch.padded_trip == ch.num_chunks * ch.chunk
    # heaviest device owns the most chunks
    counts = [ch.owners.count(d) for d in range(4)]
    assert counts[0] == max(counts) and counts[0] > counts[1]


def test_equal_weights_reproduce_cyclic_deal():
    cyc = _plan(29, 2, 4)
    eq = _plan(29, 2, 4, weights=[1.0, 1.0, 1.0, 1.0])
    assert eq.owners == tuple(j % 4 for j in range(eq.real_chunks))
    assert eq.local_chunks == cyc.local_chunks
    assert eq.num_chunks == cyc.num_chunks
    # slot q*P+d holds global chunk q*P+d — the cyclic identity
    k = eq.real_chunks
    for s, j in enumerate(eq.slot_map):
        assert j == (s if s < k else k)


def test_weighted_plan_owner_lookup():
    ch = _plan(20, 2, 2, weights=[3.0, 1.0])
    for it in range(20):
        j = it // 2
        assert ch.owner_of_iteration(it) == ch.owners[j]


def test_weighted_roundtrip_pad_unpad():
    from repro.core import nest

    ch = _plan(23, 3, 4, weights=[2.0, 1.0, 0.5, 1.0])
    x = np.arange(23, dtype=np.float32) * 1.5
    staged = nest.pad_reshape(jnp.asarray(x), ch)
    assert staged.shape == (ch.local_chunks, ch.num_devices, ch.chunk)
    back = nest.unpad_flat(staged, ch, 23)
    np.testing.assert_array_equal(np.asarray(back), x)


# ------------------------------------------------------------ validation --


def test_weights_rejected_for_wrong_lowerings():
    n = 8

    @omp.parallel_for(stop=n, name="wv")
    def blk(i, env):
        return {"y": omp.at(i, env["x"][i] + 1.0)}

    with pytest.raises(omp.CompileError, match="chunk_weights"):
        omp.Options(lowering="master_worker", chunk_weights=[1.0, 1.0])
    with pytest.raises(omp.CompileError, match="chunk_weights"):
        omp.Options(lowering="pallas", chunk_weights=[1.0, 1.0])

    @omp.parallel_for(stop=n, name="wv2")
    def blk2(i, env):
        return {"z": omp.at(i, env["y"][i] * 2.0)}

    reg = omp.region(blk, blk2, name="wvreg")
    mesh = make_mesh((1,), ("data",))
    with pytest.raises(omp.CompileError, match="COLLECTIVE"):
        omp.compile(reg, mesh, lowering="fused", chunk_weights=[1.0])


def test_weights_length_must_match_mesh():
    n = 8

    @omp.parallel_for(stop=n, name="wl")
    def blk(i, env):
        return {"y": omp.at(i, env["x"][i] + 1.0)}

    mesh = make_mesh((1,), ("data",))
    with pytest.raises(omp.CompileError, match="entries"):
        omp.compile(blk, mesh, lowering="collective",
                    chunk_weights=[1.0, 2.0],
                    env_like={"x": jnp.zeros(n), "y": jnp.zeros(n)})


def test_degenerate_weight_values_rejected():
    with pytest.raises(omp.CompileError):
        omp.Options(chunk_weights=[1.0, 0.0])
    with pytest.raises(omp.CompileError):
        omp.Options(chunk_weights=[1.0, -1.0])
    with pytest.raises(omp.CompileError):
        omp.Options(chunk_weights=[])
    with pytest.raises(omp.CompileError):
        omp.Options(chunk_weights=[float("nan"), 1.0])


# ---------------------------------------------------------- differential --


def run_weighted_sweep() -> None:
    """Subprocess entry (8 virtual devices): weighted compiles of every
    rank-1 and rank-2 family match the unweighted compile bit-for-bit
    and the shared-memory reference.

    Against the reference, matmul families may differ by a few ulp: the
    distributed program computes each chunk's dot at the chunk's shape,
    and XLA accumulates it in another order than the reference's one
    dot over the whole iteration space.  The unweighted compile differs
    from the reference in exactly the same elements, so the weighted
    deal itself moves no bit."""
    from tests.test_differential import FAMILIES, FAMILIES2, make_case, make_case2

    W8 = [2.0, 1.0, 1.0, 0.5, 1.0, 3.0, 1.0, 0.25]
    MATMUL_ULP = 4

    def red_keys(prog):
        stages = getattr(prog, "stages", None)
        loops = prog.loops if stages is not None else (prog,)
        keys = set()
        for lp in loops:
            keys |= set(getattr(lp, "reduction", {}) or {})
        return keys

    def check(prog, env, mesh, weights, tag):
        ref = prog(env)
        unw = omp.compile(prog, mesh, lowering="collective")(env)
        out = omp.compile(prog, mesh, lowering="collective",
                          chunk_weights=weights)(env)
        reds = red_keys(prog)
        for k in ref:
            got, want = np.asarray(out[k]), np.asarray(ref[k])
            if k in reds:
                np.testing.assert_allclose(
                    got, want, rtol=1e-5, atol=1e-6, err_msg=f"{tag} key={k!r}")
                continue
            np.testing.assert_array_equal(
                got, np.asarray(unw[k]), err_msg=f"{tag} vs unweighted key={k!r}")
            if "matmul" in tag:
                np.testing.assert_array_max_ulp(got, want, maxulp=MATMUL_ULP)
            else:
                np.testing.assert_array_equal(got, want,
                                              err_msg=f"{tag} key={k!r}")

    mesh = make_mesh((8,), ("data",))
    for fi, fam in enumerate(FAMILIES):
        prog, env, fam = make_case(8800 + fi, family=fam)
        check(prog, env, mesh, W8, f"r1:{fam}")
    print("weighted1:", ",".join(FAMILIES))

    mesh2 = make_mesh((4, 2), ("i", "j"))
    per_axis = ([3.0, 1.0, 1.0, 1.0], None)
    for fj, fam in enumerate(FAMILIES2):
        prog, env, fam = make_case2(8900 + fj, family=fam)
        check(prog, env, mesh2, per_axis, f"r2:{fam}")
        check(prog, env, mesh2, ([1.0, 1.0, 2.0, 1.0], [1.0, 4.0]),
              f"r2b:{fam}")
    print("weighted2:", ",".join(FAMILIES2))
    print("OKWEIGHTED")


def test_weighted_schedule_differential(multidevice):
    out = multidevice(f"""
        import sys
        sys.path.insert(0, {REPO!r})
        from tests.test_weighted_schedule import run_weighted_sweep
        run_weighted_sweep()
    """, n_devices=8)
    assert "OKWEIGHTED" in out
    assert "weighted1:" in out and "weighted2:" in out


def test_weighted_schedule_changes_ownership_in_plan():
    """The weights land in the emitted program: the schedule pass
    artifact carries the re-dealt owners."""
    n = 24

    @omp.parallel_for(stop=n, name="wplan", schedule=omp.dynamic(2))
    def blk(i, env):
        return {"y": omp.at(i, env["x"][i] * 2.0)}

    env = {"x": jnp.arange(n, dtype=jnp.float32),
           "y": jnp.zeros(n, jnp.float32)}
    mesh = make_mesh((1,), ("data",))
    c = omp.compile(blk, mesh, lowering="collective",
                    chunk_weights=[1.0], env_like=env)
    (ch,) = c.passes[1].output
    assert ch.weights == (1.0,)
    assert ch.owners is not None and ch.slot_map is not None
