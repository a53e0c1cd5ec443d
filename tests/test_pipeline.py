"""Mode B shard_map pipeline: loss/grad equivalence with the sequential
model, parametrized over stage counts (1 = degenerate single-stage, 2, 4)
crossed with uneven layer counts so the padded-slot path is exercised at
every width: (1,3) lps=3 pad=0, (2,5) lps=3 pad=1, (4,6) lps=2 pad=2.
Each combo runs in its own subprocess so the 8 host devices don't leak
into the main pytest process (which must keep 1 device per spec)."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    STAGES = int(os.environ["PP_STAGES"])
    LAYERS = int(os.environ["PP_LAYERS"])
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.configs.base import get_config
    from repro.models import model as M
    from repro.parallel import pipeline as PP

    cfg = dataclasses.replace(get_config('granite-3-8b').reduced(),
                              n_layers=LAYERS, vocab_size=128)
    pcfg = PP.PipelineConfig(n_stages=STAGES, n_micro=4)
    lps, pad = PP.layers_per_stage(cfg, pcfg)
    mesh = jax.make_mesh((1, 2, STAGES), ("clusters", "data", "model"))

    params = PP.init_pp_params(cfg, jax.random.PRNGKey(0), pcfg)
    paramsC = jax.tree.map(lambda x: x[None], params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 8, 16), 0,
                                cfg.vocab_size)

    loss_fn = PP.make_pp_loss(cfg, mesh, pcfg, cluster_stacked=True)
    loss_pp = float(jax.jit(loss_fn)(paramsC, tokens))

    def ref_loss_from_pp(pC):
        rp = PP.sequential_params(jax.tree.map(lambda x: x[0], pC), cfg)
        return M.loss_fn(rp, cfg, {"tokens": tokens[0]}, remat=False)[0]

    ref = float(ref_loss_from_pp(paramsC))
    assert abs(loss_pp - ref) < 1e-4, (loss_pp, ref)

    g_pp = jax.jit(jax.grad(loss_fn))(paramsC, tokens)
    g_ref = jax.jit(jax.grad(ref_loss_from_pp))(paramsC)
    errs = {}
    flat_pp, _ = jax.tree_util.tree_flatten_with_path(g_pp)
    flat_rf = jax.tree.leaves(g_ref)
    for (path, a), b in zip(flat_pp, flat_rf):
        name = jax.tree_util.keystr(path)
        if "active" in name:
            continue                       # mask is not a trainable param
        errs[name] = float(jnp.abs(a - b).max())
    worst = max(errs.values())
    assert worst < 1e-3, errs
    print(f"PIPELINE-EQUIV-OK stages={STAGES} layers={LAYERS} "
          f"lps={lps} pad={pad} loss={loss_pp} worst_grad_err={worst}")
""")


@pytest.mark.slow
@pytest.mark.parametrize("stages,layers", [(1, 3), (2, 5), (4, 6)])
def test_pipeline_matches_sequential(stages, layers):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PP_STAGES"] = str(stages)
    env["PP_LAYERS"] = str(layers)
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert f"PIPELINE-EQUIV-OK stages={stages} layers={layers}" in r.stdout
