"""The names a device trace reads back: region scopes in the three jitted
steps, the serve engine's spans, and the launchers' spans on the
profiler's clock.

A region is a ``jax.named_scope`` named ``<part>.<region>``; it lands in
each op's path through ``jvp``, ``transpose``, ``checkpoint``, ``vmap``
and ``scan``.  The steps are compiled here at a tiny size and the names
read from the compiled program's op metadata, which a profile reports as
each op's ``tf_op``.
"""
import contextlib
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ShapeConfig, get_config

SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")


@pytest.fixture(scope="module")
def cfg():
    return get_config("opt-1.3b").reduced()


def _locations(lowered):
    """Every op path the compiled program carries."""
    return set(re.findall(r'op_name="([^"]*)"',
                          lowered.compile().as_text()))


def _regions(path):
    return re.findall(r"(?<![\w.])[A-Za-z_]\w*\.[A-Za-z_]\w*(?![\w.])",
                      path)


def _paths_with(locs, *regions):
    return [p for p in locs if all(r in _regions(p) for r in regions)]


def test_train_step_names_its_regions_forward_and_backward(cfg):
    from repro.launch import steps

    p = steps.params_specs(cfg, n_clusters=2)
    opt = steps.opt_specs(p)
    batch = steps.input_specs(cfg, ShapeConfig("t", 64, 4, "train"),
                              n_clusters=2)
    locs = _locations(jax.jit(steps.make_train_step(cfg)).lower(
        p, opt, batch))
    for r in ("model.embed", "model.layers", "model.attn", "model.ffn",
              "model.head", "train.adamw"):
        assert _paths_with(locs, r), r
    attn = _paths_with(locs, "model.attn")
    # the layers inside the layer stack's loop, the clusters under vmap
    assert all("model.layers" in _regions(p) for p in attn if "/while/" in p)
    assert any("vmap(" in p and "/while/" in p for p in attn)
    # the backward (transpose of the jvp) and the recomputed forward of
    # the checkpointed layer keep the region
    assert any("transpose(" in p for p in attn)
    assert any("rematted_computation" in p for p in attn)
    assert any("transpose(" in p for p in _paths_with(locs, "model.head"))
    assert not any("transpose(" in p
                   for p in _paths_with(locs, "train.adamw"))


def test_outer_step_names_the_compressor_and_the_update(cfg):
    from repro.core import mesh_compression as mc
    from repro.launch import steps

    ccfg = mc.MeshCompressionConfig(rank=8, min_dim_for_lowrank=16)
    p = steps.params_specs(cfg, n_clusters=2)
    ost = steps.outer_state_specs(cfg, 2, ccfg)
    locs = _locations(jax.jit(steps.make_outer_step(cfg, ccfg)).lower(
        p, ost, jax.ShapeDtypeStruct((), jnp.int32)))
    for r in ("outer.compress", "outer.update"):
        assert _paths_with(locs, r), r
    # the Cholesky-QR and the int4 packing inside the compressor
    for r in ("outer.orthonormalize", "outer.quant"):
        assert _paths_with(locs, r)
        assert all("outer.compress" in _regions(p)
                   for p in _paths_with(locs, r))
    assert not _paths_with(locs, "outer.compress", "outer.update")


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_decode_step_names_its_regions(cfg, backend):
    from repro.models import model as M
    from repro.serve.engine import init_kv_pages, make_paged_decode_step

    S, pages = 4, 8
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    caches = jax.eval_shape(lambda: init_kv_pages(cfg, n_pages=S * pages,
                                                  page_size=4))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    lowered = jax.jit(make_paged_decode_step(cfg, backend=backend)).lower(
        params, caches, i32(S), i32(S), jax.ShapeDtypeStruct((S,), bool),
        i32(S, pages))
    locs = _locations(lowered)
    for r in ("model.embed", "model.head", "decode.layers", "model.attn",
              "model.ffn", "decode.kv_write", "decode.paged_attention"):
        assert _paths_with(locs, r), r
    # the layer scan holds the layers; the cache's writes and reads are
    # inside each layer's attention
    assert _paths_with(locs, "decode.layers", "model.attn",
                       "decode.kv_write")
    assert _paths_with(locs, "decode.layers", "model.attn",
                       "decode.paged_attention")
    assert _paths_with(locs, "decode.layers", "model.ffn")
    assert not _paths_with(locs, "decode.layers", "model.head")
    if backend == "pallas":
        assert any("decode.paged_attention/paged_attention" in p
                   for p in locs)


def test_engine_step_spans_nest_in_order(cfg):
    from repro.models import model as M
    from repro.serve.engine import ServeEngine

    log = []

    @contextlib.contextmanager
    def span(name, **_):
        log.append(("enter", name))
        yield
        log.append(("exit", name))

    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(params, cfg, max_seqs=2, page_size=4, n_pages=8,
                      max_pages_per_seq=4, eos_id=None, span=span)
    eng.submit([1, 2, 3], 2)
    assert eng.step()
    assert log == [("enter", "admit"), ("exit", "admit"),
                   ("enter", "plan"), ("exit", "plan"),
                   ("enter", "device_step"),
                   ("enter", "put"), ("exit", "put"),
                   ("enter", "dispatch"), ("exit", "dispatch"),
                   ("enter", "fetch"), ("exit", "fetch"),
                   ("exit", "device_step"),
                   ("enter", "commit"), ("exit", "commit")]


def test_engine_without_a_span_hook_serves_as_before(cfg):
    from repro.models import model as M
    from repro.serve.engine import ServeEngine

    params = M.init_params(cfg, jax.random.PRNGKey(0))
    out = []
    for span in (None, lambda name, **_: contextlib.nullcontext()):
        eng = ServeEngine(params, cfg, max_seqs=2, page_size=4, n_pages=8,
                          max_pages_per_seq=4, eos_id=None, span=span)
        req = eng.submit([5, 6, 7], 3)
        eng.run()
        out.append(list(req.generated))
    assert out[0] == out[1] and len(out[0]) == 3


def test_tracer_spans_enter_the_profiler_once_jax_is_imported(monkeypatch):
    from repro.obs import Tracer

    entered = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            entered.append("/" + self.name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    tr = Tracer("launcher")
    with tr.span("round", round=0):
        with tr.span("inner"):
            pass
    assert entered == ["round", "inner", "/inner", "/round"]
    assert [e["name"] for e in tr.events] == ["inner", "round"]
    assert tr.events[1]["args"] == {"round": 0}


def _run_without_jax(code: str) -> str:
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip()


def test_import_obs_imports_no_jax():
    assert _run_without_jax(
        "import sys, repro.obs, repro.obs.profile\n"
        "print('jax' in sys.modules)") == "False"


def test_tracer_spans_without_jax_stay_off_the_profiler():
    assert _run_without_jax(
        "import sys\n"
        "from repro.obs import Tracer\n"
        "t = Tracer()\n"
        "with t.span('round'):\n"
        "    pass\n"
        "print(len(t.events), 'jax' in sys.modules)") == "1 False"


def test_profile_keeps_only_the_capture():
    from repro.obs import profile

    assert not hasattr(profile, "scope")
    assert not hasattr(profile, "annotate")
    assert not hasattr(profile, "enabled")
    with profile.capture("unused"):       # REPRO_PROFILE unset: a no-op
        x = np.ones(2).sum()
    assert x == 2
