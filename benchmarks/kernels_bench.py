"""Kernel microbenchmarks: us/call for the compressor/attention hot spots,
jnp reference path vs Pallas interpret path (interpret mode measures the
Python-executed kernel body — correctness-lane numbers, not TPU numbers;
the BlockSpec tiling is what carries to hardware).

The ``outer_step_*`` section times the full Alg. 1 compressor for one
parameter matrix three ways:

  outer_step_unfused_*   the ref op-chain dispatched op by op (each arrow
                         its own XLA call, every intermediate crossing
                         HBM) — the pre-fusion production shape of the
                         compressor and the "before" side of the tentpole
  outer_step_refjit_*    the same chain under one jax.jit (XLA's own
                         partial fusion — the strongest CPU baseline)
  outer_step_fused_*     the fused Pallas pipeline (kernels/fused_compress)

Shapes are 107B-config per-device shards: d_model 8192 / d_ff 24576 with
4-way tensor sharding gives (2048, 2048) and (2048, 6144) matrices, and
the paper's rank-2048 compressor sharded the same way gives r = 512/1024.
"""
from __future__ import annotations

import time
from typing import Dict

import jax
import jax.numpy as jnp


def _time(fn, *args, iters: int = 5) -> float:
    # Block on the warm-up so the first timed iteration doesn't absorb
    # in-flight compile/compute, and on every timed dispatch so each
    # iteration pays its full cost (async dispatch otherwise overlaps
    # them and only the last sync is honest).
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def _outer_step_bench(out: Dict[str, float], smoke: bool) -> None:
    """Fused-vs-unfused Alg. 1 outer-step compressor (the tentpole's
    before/after numbers)."""
    from repro.kernels import ref
    from repro.kernels.fused_compress import fused_compress_ef

    shapes = ([(256, 256, 32)] if smoke else
              [(2048, 2048, 512), (2048, 6144, 512), (2048, 2048, 1024)])
    iters = 2 if smoke else 3
    for m, n, r in shapes:
        d = jax.random.normal(jax.random.PRNGKey(0), (m, n), jnp.float32)
        e = jax.random.normal(jax.random.PRNGKey(1), (m, n),
                              jnp.float32) * 0.1
        q = jax.random.normal(jax.random.PRNGKey(2), (n, r), jnp.float32)

        def unfused(d_, e_, q_):
            # eager: every chain op is its own XLA dispatch
            return ref.outer_step_ref(d_, e_, q_)[:3]

        refjit = jax.jit(lambda d_, e_, q_: ref.outer_step_ref(d_, e_, q_))
        fused = jax.jit(lambda d_, e_, q_: fused_compress_ef(d_, e_, q_))

        tag = f"{m}x{n}_r{r}"
        t_unf = _time(unfused, d, e, q, iters=iters)
        t_jit = _time(refjit, d, e, q, iters=iters)
        t_fus = _time(fused, d, e, q, iters=iters)
        out[f"outer_step_unfused_{tag}"] = t_unf
        out[f"outer_step_refjit_{tag}"] = t_jit
        out[f"outer_step_fused_{tag}"] = t_fus
        out[f"outer_step_fused_speedup_{tag}"] = t_unf / t_fus


def run(smoke: bool = False) -> Dict[str, float]:
    """``smoke``: shrink inputs and skip the slowest Pallas interpret paths
    (their Python-executed kernel bodies are the slow part) — a
    seconds-scale bit-rot check of every jnp reference path for CI."""
    from repro.kernels import ref

    out = {}
    n = 1 << 16 if smoke else 1 << 20
    tag = "64k" if smoke else "1M"
    x = jax.random.normal(jax.random.PRNGKey(0), (n,))
    out[f"quant4_pack_ref_{tag}"] = _time(
        jax.jit(lambda v: ref.quant4_pack_ref(v)[0]), x)

    d = 256 if smoke else 1024
    a = jax.random.normal(jax.random.PRNGKey(1), (d, d))
    b = jax.random.normal(jax.random.PRNGKey(2), (d, 128))
    out[f"powersgd_proj_ref_{d}x{d}xr128"] = _time(
        jax.jit(ref.matmul_ref), a, b)

    s = 256 if smoke else 1024
    q = jax.random.normal(jax.random.PRNGKey(3), (1, s, 4, 64))
    k = jax.random.normal(jax.random.PRNGKey(4), (1, s, 1, 64))
    out[f"flash_attn_ref_{s}"] = _time(
        jax.jit(lambda q_, k_, v_: ref.flash_attention_ref(q_, k_, v_)),
        q, k, k)

    _outer_step_bench(out, smoke)

    if not smoke:
        from repro.kernels.lowrank_mm import matmul_pallas
        from repro.kernels.quant4 import quant4_pack_pallas
        out["quant4_pack_pallas_1M"] = _time(
            lambda v: quant4_pack_pallas(v)[0], x, iters=2)
        out["powersgd_proj_pallas"] = _time(matmul_pallas, a, b, iters=2)
    return out


if __name__ == "__main__":
    for k, v in run().items():
        print(f"{k},{v:.1f},us_per_call")
