"""The in-process benchmark sections of ``benchmarks/run.py``.

``python benchmarks/sections.py <section>`` runs one section in this
process and prints its ``name,us_per_call,derived`` CSV rows; lines
starting with ``#`` carry metadata for the harness (the device count,
the compile-cache totals).  ``benchmarks/run.py`` starts one such
process per section and never touches JAX itself, so each section owns
its devices.  Sections: polybench, compile_cache, kernels, lm.
"""
from __future__ import annotations

import json
import os
import sys
import time

# Make ``benchmarks.*`` importable in script mode.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from repro import jax_cache  # noqa: E402

jax_cache.enable()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def _timeit(fn, *args, warmup=2, iters=5):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - t0)
    return best * 1e6  # us


def _row(name, us, derived=""):
    print(f"{name},{us:.1f},{derived}", flush=True)


# ---------------------------------------------------------------------------
# Polybench (paper Fig. 6)
# ---------------------------------------------------------------------------


def _projected_speedup(programs, env, ranks=64, flops_time_us=None):
    """T_1 / (T_1/P + comm/link_bw): the Fig. 6 projection."""
    from repro.core.plan import make_plan
    from repro.core.report import _comm_summary

    comm_bytes = 0
    for prog in programs:
        plan = make_plan(prog, env, ranks)
        line = _comm_summary(plan)[-1]
        comm_bytes += int(line.split("~")[1].split()[0])
    t1 = (flops_time_us or 1.0) * 1e-6
    tp = t1 / ranks + comm_bytes / 50e9
    return t1 / tp


def bench_polybench():
    from benchmarks.polybench import ALL_KERNELS
    from repro import omp
    from repro.compat import make_mesh

    mesh = make_mesh((len(jax.devices()),), ("data",))

    for make in ALL_KERNELS:
        k = make()
        env = k.env_fn(k.n)

        def run_seq(env=env, k=k):
            out = dict(env)
            for prog in k.programs:
                # sequential: lax.map over iterations (one at a time)
                loop_out = out
                t = prog.stop - prog.start
                idx = prog.start + jnp.arange(t) * prog.step
                vals = jax.lax.map(lambda i: prog.body(i, loop_out), idx)
                from repro.core import pragma, reduction as red_mod

                for key, upd in vals.items():
                    if isinstance(upd, pragma.At):
                        loop_out[key] = loop_out[key].at[upd.idx].set(
                            upd.value)
                    elif isinstance(upd, pragma.Red):
                        rop = red_mod.get_reduction(prog.reduction[key])
                        folded = rop.local_fold(upd.value, 0)
                        loop_out[key] = rop.pairwise(loop_out[key], folded)
                out = loop_out
            return out

        def run_omp(env=env, k=k):
            out = dict(env)
            for prog in k.programs:
                out = prog(out)
            return out

        dists = [omp.compile(p, mesh) for p in k.programs]

        def run_mpi(env=env, dists=dists):
            out = dict(env)
            for d in dists:
                out = d(out)
            return out

        seq_j = jax.jit(run_seq)
        omp_j = jax.jit(run_omp)
        mpi_j = jax.jit(run_mpi)

        ref = omp_j(env)
        got = mpi_j(env)
        for key in k.check_keys:
            np.testing.assert_allclose(np.asarray(got[key]),
                                       np.asarray(ref[key]),
                                       rtol=1e-3, atol=1e-3)

        us_seq = _timeit(seq_j)
        us_omp = _timeit(omp_j)
        us_mpi = _timeit(mpi_j)
        # Fig. 6 analogue: projected speed-up of the generated program on
        # 64 ranks vs the SEQUENTIAL baseline (the paper's y-axis)
        proj = _projected_speedup(k.programs, env, ranks=64,
                                  flops_time_us=us_seq)
        _row(f"polybench_{k.name}_seq", us_seq)
        _row(f"polybench_{k.name}_omp", us_omp,
             f"speedup_vs_seq={us_seq / us_omp:.2f}")
        _row(f"polybench_{k.name}_mpi", us_mpi,
             f"proj_speedup64_vs_seq={proj:.1f};overhead_vs_omp="
             f"{us_mpi / us_omp:.2f}")


# ---------------------------------------------------------------------------
# Compilation cache (omp.compile cold vs warm)
# ---------------------------------------------------------------------------

def bench_compile_cache():
    """Cold vs warm ``omp.compile``: the structural compilation cache
    must make repeated compiles (benchmark sweeps, the differential
    harness) skip re-planning entirely."""
    from benchmarks.polybench import ALL_KERNELS
    from repro import omp
    from repro.compat import make_mesh

    mesh = make_mesh((len(jax.devices()),), ("data",))
    cold_us = warm_us = 0.0
    n_programs = 0
    omp.clear_compile_cache()
    for make in ALL_KERNELS:
        k = make()
        env = k.env_fn(k.n)
        for prog in k.programs:
            n_programs += 1
            t0 = time.perf_counter()
            omp.compile(prog, mesh, env_like=env)
            cold_us += (time.perf_counter() - t0) * 1e6
            t0 = time.perf_counter()
            c = omp.compile(prog, mesh, env_like=env)
            warm_us += (time.perf_counter() - t0) * 1e6
            assert c.cache_hit, f"warm compile of {prog.name} missed the cache"
            env = prog(env)  # next block sees this block's outputs
    stats = omp.compile_cache_stats()
    speedup = cold_us / max(warm_us, 1e-9)
    print("#compile_cache " + json.dumps({
        "n_programs": n_programs,
        "cold_us_total": round(cold_us, 1),
        "warm_us_total": round(warm_us, 1),
        "speedup": round(speedup, 1),
        "hits": stats["hits"],
        "misses": stats["misses"],
    }), flush=True)
    _row("compile_cache_cold", cold_us / n_programs,
         f"programs={n_programs}")
    _row("compile_cache_warm", warm_us / n_programs,
         f"speedup={speedup:.1f};hits={stats['hits']};"
         f"misses={stats['misses']}")


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------


def bench_kernels():
    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    b, s, h, kv, hd = 1, 256, 4, 2, 64
    q = jnp.asarray(rng.normal(size=(b, s, h, hd)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(b, s, kv, hd)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(b, s, kv, hd)).astype(np.float32))
    us = _timeit(lambda: ops.flash_attention(q, k, v, kind="causal"))
    ref_us = _timeit(jax.jit(lambda: ref.flash_attention_ref(
        q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2))))
    _row("kernels_flash_attention_interp", us,
         f"oracle_us={ref_us:.0f}")

    x = jnp.asarray(rng.normal(size=(1, 256, 2, 32)).astype(np.float32))
    dt = jnp.abs(jnp.asarray(rng.normal(size=(1, 256, 2))
                             .astype(np.float32))) * 0.1
    A = jnp.asarray((-np.abs(rng.normal(size=(2,))) - 0.1)
                    .astype(np.float32))
    Bm = jnp.asarray(rng.normal(size=(1, 256, 16)).astype(np.float32))
    Cm = jnp.asarray(rng.normal(size=(1, 256, 16)).astype(np.float32))
    D = jnp.asarray(rng.normal(size=(2,)).astype(np.float32))
    us = _timeit(lambda: ops.ssd_scan(x, dt, A, Bm, Cm, D, chunk=64))
    ref_us = _timeit(jax.jit(lambda: ref.ssd_ref(x, dt, A, Bm, Cm, D)[0]))
    _row("kernels_ssd_scan_interp", us, f"oracle_us={ref_us:.0f}")


# ---------------------------------------------------------------------------
# LM steps (smoke size)
# ---------------------------------------------------------------------------


def bench_lm_steps():
    from repro.configs import smoke_config
    from repro.models import build_model

    for arch in ("gemma3-1b", "mamba2-130m", "qwen2-moe-a2.7b"):
        cfg = smoke_config(arch)
        model = build_model(cfg)
        params, _ = model.init(jax.random.PRNGKey(0))
        b, s = 2, 128
        key = jax.random.PRNGKey(1)
        batch = {"tokens": jax.random.randint(key, (b, s), 0,
                                              cfg.vocab_size),
                 "labels": jax.random.randint(key, (b, s), 0,
                                              cfg.vocab_size)}
        loss_j = jax.jit(lambda p, bt: model.loss_fn(p, bt)[0])
        us = _timeit(loss_j, params, batch)
        _row(f"loss_{arch}", us, f"tokens={b * s}")

        cache = model.init_cache(b, 64, dtype=jnp.float32)
        dec_j = jax.jit(lambda p, c, t, q: model.decode_step(p, c, t, q))
        tok = jnp.zeros((b,), jnp.int32)
        pos = jnp.full((b,), 1, jnp.int32)
        # decode donates nothing here; measure steady-state step
        us = _timeit(dec_j, params, cache, tok, pos)
        _row(f"decode_{arch}", us, "cache_len=64")


SECTIONS = {
    "polybench": bench_polybench,
    "compile_cache": bench_compile_cache,
    "kernels": bench_kernels,
    "lm": bench_lm_steps,
}


def main(argv=None) -> None:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1 or argv[0] not in SECTIONS:
        sys.exit(f"usage: sections.py {{{','.join(SECTIONS)}}}")
    print(f"#device_count {len(jax.devices())}", flush=True)
    SECTIONS[argv[0]]()


if __name__ == "__main__":
    main()
