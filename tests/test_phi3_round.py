"""Two DiLoCoX rounds of a small Phi-3-shaped model (GQA 4:1, SwiGLU,
RMSNorm, untied head) on a 2 clusters x 1 x 2 model ranks mesh of four
host devices, through ``steps.round_shardings``, ``make_train_step`` and
``make_outer_step`` as the benchmark's ``train_rounds.Program`` composes
them, against the plain reference (``bench/harness/reference.py``) run by
``train_rounds_mesh.reference_rounds`` over the same four devices, from the
same seeded weights and tokens.

The devices must be asked for before JAX starts, so the rounds run in a
subprocess, once for the module; the planted fault (an outer step that
averages nothing: every cluster sent cluster 0's pseudo-gradient) runs in
the same one."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [os.path.join(ROOT, "bench"), os.path.join(ROOT, "src")]
    import jax, jax.numpy as jnp, numpy as np
    from harness import cell as cells, compare, train_rounds as T
    from harness import train_rounds_mesh as R
    from repro.core import mesh_compression as mc

    H = 2
    model = dict(n_layers=1, d_model=64, n_heads=8, n_kv_heads=2,
                 head_dim=16, d_ff=160, vocab_size=256, norm="rmsnorm",
                 norm_eps=1e-6, mlp="swiglu", rope_theta=10000.0,
                 tie_embeddings=False, param_dtype="float32",
                 compute_dtype="float32")
    traffic = dict(kind="train_rounds_mesh", clusters=2, data=1,
                   model_ranks=2, h=H, rank=8, batch=2, seq_len=32,
                   inner_lr=1e-3, outer_lr=0.7, outer_momentum=0.9,
                   adamw=dict(b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1,
                              grad_clip=1.0),
                   quant_block=256, lowrank_min_dim=8, batch_pool=8)
    cell = cells.Cell(name="phi3-small-test", chips=4,
                      config={"arch": "phi3-medium-14b", "model": model},
                      traffic=traffic, limits={}, end_to_end=[],
                      per_layer=[])
    key, _ = cells.seed_streams(2 ** 31 + 15)

    def program():
        prog = T.Program(cell, key)
        assert prog.params["embed"].sharding.spec[0] == "clusters"
        losses, obs = [], {}
        for r in range(2):
            for h in range(H):
                losses.append(prog.inner_step())
                if prog.steps_done == 1:
                    obs["grad"] = prog._grad_norms(prog.opt.m)
            prog.outer_step()
        # round 1's outer step is the first to average anything
        obs["outer"] = prog._move_norms(prog.ost.anchor)
        obs["losses"] = losses
        obs = {k: np.asarray(v, np.float64).tolist()
               for k, v in jax.device_get(obs).items()}
        prog.free()
        return obs

    def gaps(obs, ref):
        return {"loss": compare.rel_gap(obs["losses"], ref["losses"]),
                "grad": compare.leaf_norm_gap(obs["grad"], ref["grad"],
                                              ref["grad"]),
                "outer": compare.leaf_norm_gap(obs["outer"], ref["outer"],
                                               ref["grad"])}

    ref = R.reference_rounds(cell, key, jax.devices()[:4], 2)
    out = {"sound": gaps(program(), ref)}
    mean = mc.compress_gather_mean

    def unmixed(delta, q, rank, cfg):
        first = jax.tree.map(lambda d: jnp.broadcast_to(d[:1], d.shape),
                             delta)
        return mean(first, q, rank, cfg)

    mc.compress_gather_mean = unmixed
    out["fault"] = gaps(program(), ref)
    print("ROUNDS " + json.dumps(out))
""")

# Program and reference agree up to float32 rounding in another order of
# summation (GSPMD's split sums over two model ranks, the program's
# sequence-chunked head and fused AdamW): 1.2e-7 relative in the losses
# and 1.3e-7 in the gradient norms here; 1e-4 leaves three decades.  The
# anchor's move passes through int4 codes, where such rounding can flip a
# code at a rounding boundary (one step of a block's scale in one element
# of 256); it reads 2.5e-6, held to 1e-3, beside 0.45 for the planted
# fault.
TOL = {"loss": 1e-4, "grad": 1e-4, "outer": 1e-3}


@pytest.fixture(scope="module")
def rounds():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", f"ROOT = {ROOT!r}\n" + SCRIPT],
                       env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-4000:]
    line = [x for x in r.stdout.splitlines() if x.startswith("ROUNDS ")]
    return json.loads(line[-1][len("ROUNDS "):])


def test_two_rounds_match_the_reference(rounds):
    for name, tol in TOL.items():
        assert rounds["sound"][name] <= tol, rounds["sound"]


def test_an_outer_step_without_the_cluster_mean_fails(rounds):
    fault = rounds["fault"]
    # the inner steps and round 0's exchange of zeros are untouched
    assert fault["loss"] <= TOL["loss"] and fault["grad"] <= TOL["grad"]
    assert fault["outer"] > 10 * TOL["outer"], fault
