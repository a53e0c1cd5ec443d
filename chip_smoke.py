"""Check that DiLoCoX training and paged serving run on a TPU.

    python chip_smoke.py               # one chip: kernels, serve, train
    python chip_smoke.py --four-chips  # four chips: the sharded train paths

One chip, in one process and in this order (ascending device memory):

  kernels  every Pallas kernel of the two paths, compiled for the chip, run
           once against its jnp reference at OPT-1.3B shapes; the worst
           error is printed next to the bound ``tests/test_kernels.py``
           holds the kernel to.
  serve    ``launch/serve.py --paged --backend pallas`` at full OPT-1.3B
           (24 layers): 8 requests, 128-token prompts, 32 new tokens.  Then
           the same requests through a second engine that keeps its logits:
           every step's logits, at every prompt and generated position,
           against the dense model's teacher-forced logits on the same
           sequences, both at f32 matmul precision.
  train    ``launch/train.py``'s GSPMD DiLoCoX rounds at OPT-1.3B width on a
           1 x 1 x 1 mesh, depth cut to ``TRAIN_LAYERS``: H=2 inner AdamW
           steps and the compressed, delayed-Nesterov outer step per round.
           The first loss must match ``loss_fn`` at the initial params, the
           losses must be finite, and the loss on held-out batches must
           fall from the initial to the final params.

``--four-chips`` runs only what exists across chips, at the same width and
depth:

  mesh     GSPMD training on 2 clusters x 2 model ranks (four chips)
           against 2 clusters x 1 model rank (two chips), same seed and data,
           ``MESH_ROUNDS`` rounds: per-round losses agree, and on each
           mesh the held-out loss at the final outer params is below its
           value at the initial ones.
  pp       ``--inner pp`` on 2 clusters x 2 pipeline stages (four chips):
           the first step's loss against the unpipelined ``loss_fn`` on the
           same parameters; then the same comparisons against 2 clusters x
           1 stage (two chips).

The outer step applies the previous round's averaged delta (one-step
delay), and round 0's pending delta is zero: round 1's outer step is the
first to move the params, so round 2's losses are the first to depend on
the exchange between clusters.

Every phase prints its lines; the last line of stdout is one JSON object,
``{"ok": true, "device": {...}}``.  With no TPU, or when any check fails,
the script exits non-zero and prints no such line.  It starts no other
process: one process drives every chip it uses.
"""
import argparse
import dataclasses
import json
import math
import os
import sys
import time

# Depth of the training phase.  From ``compiled.memory_analysis()`` of the
# train and outer steps compiled for a v5e (``tests/test_tpu_compile.py``
# keeps it true): the ~7 resident f32 copies of the round state plus the
# train step's temporaries take ~12.2 GiB of the chip's 16 GiB at 2 layers
# and ~15.4 GiB at 3.
TRAIN_LAYERS = 2
TRAIN_BATCH = 2             # sequences per cluster per inner step
SEQ_LEN = 2048              # OPT-1.3B's context
RANK = 64                   # PowerSGD rank of the outer compressor
# Half OPT-1.3B's published peak (2e-4, reached after warmup there): with
# no warmup, 1e-3 made every inner step raise the loss at this width and
# the outer steps amplified it (one v5e chip run)
INNER_LR = 1e-4

# bounds from tests/test_kernels.py
PAGED_TOL = (1e-3, 1e-5)    # (rtol, atol)
FLASH_TOL = (2e-3, 2e-3)
ULP_K = 16                  # fused recon: ULP_K * eps * (|Pq| @ |Qq|^T)

# held-out data: shards far from any cluster's own stream
HELD_OUT_SHARD = 1000
HELD_OUT_BATCHES = 4

# Bounds on one program against another at f32, where only the order of
# the arithmetic differs.  Each sits a few times above its reading (PERF.md
# lists them) and far below what a fault moves: a loss over another batch
# moves ~4.4e-4 relative.
# serving: max |engine logit - dense logit| / std of the dense logits (a
# wrong page table or a dropped key reads 2.5 or more)
SERVE_LOGIT_TOL = 2e-5
# training: the driver's first loss against ``loss_fn`` on the same params
# and batch
TRAIN_RTOL = 2e-5
# four chips: per-round losses of two meshes (round 2's follow the first
# exchange: one without the cluster mean moves them by ~3e-4), and the
# pipelined first loss
MESH_ROUNDS = 3
MESH_RTOL = 2e-5
PP_RTOL = 4e-6


def _src_path() -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")


def _say(msg: str) -> None:
    print(msg, flush=True)


def _peak_gib(dev) -> float:
    return dev.memory_stats()["peak_bytes_in_use"] / 2 ** 30


class Checks:
    """Collects named pass/fail checks and prints each as it lands."""

    def __init__(self):
        self.failed = []

    def bound(self, name: str, err: float, bound: float, what: str = ""
              ) -> None:
        ok = bool(math.isfinite(err) and err <= bound)
        _say(f"  {name}: {what}max_err={err:.3e} bound={bound:.3e} "
             f"{'ok' if ok else 'FAIL'}")
        if not ok:
            self.failed.append(name)

    def true(self, name: str, ok: bool, detail: str) -> None:
        _say(f"  {name}: {detail} {'ok' if ok else 'FAIL'}")
        if not ok:
            self.failed.append(name)


def _tol_ratio(out, ref, rtol: float, atol: float) -> float:
    """max |out - ref| / (atol + rtol |ref|): <= 1 is within the bound."""
    import numpy as np
    out, ref = np.asarray(out, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(out - ref) / (atol + rtol * np.abs(ref))))


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def check_kernels(checks: Checks) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention_pallas
    from repro.kernels.lowrank_mm import matmul_pallas
    from repro.kernels.quant4 import quant4_pack_pallas, quant4_unpack_pallas
    from repro.serve import attention_paged as ap

    key = jax.random.PRNGKey(0)
    normal = lambda i, shape: jax.random.normal(jax.random.fold_in(key, i),
                                                shape, jnp.float32)
    # the references run at full f32 matmul precision, as the kernels do
    highest = lambda: jax.default_matmul_precision("highest")

    # paged decode attention at OPT-1.3B's 32 x 64 heads: 8 sequences of up
    # to 20 pages of 8 tokens, pages scattered over the pool
    S, P, ps, H, dh = 8, 20, 8, 32, 64
    rng = np.random.default_rng(0)
    table = jnp.asarray(1 + rng.permutation(S * P).reshape(S, P), jnp.int32)
    lengths = jnp.asarray(rng.integers(0, P * ps, S), jnp.int32)
    cache = {"k": normal(1, (1 + S * P, ps, H, dh)),
             "v": normal(2, (1 + S * P, ps, H, dh))}
    q = normal(3, (S, 1, H, dh))
    out = jax.jit(ap.pallas_paged_attention)(q, cache, table, lengths)
    with highest():
        want = jax.jit(ap.ref_paged_attention)(q, cache, table, lengths)
    checks.bound("paged_attention", _tol_ratio(out, want, *PAGED_TOL), 1.0,
                 "(|err|/(atol+rtol|ref|)) ")

    # flash attention forward at OPT-1.3B's context and heads
    q, k, v = (normal(i, (1, SEQ_LEN, H, dh)) for i in (4, 5, 6))
    out = jax.jit(flash_attention_pallas)(q, k, v)
    with highest():
        want = jax.jit(ref.flash_attention_ref)(q, k, v)
    checks.bound("flash_attention", _tol_ratio(out, want, *FLASH_TOL), 1.0,
                 "(|err|/(atol+rtol|ref|)) ")

    # quant4 pack/unpack on a (2048, 64) factor's worth of values
    x = normal(7, (2048 * 64,)) * 3.0
    packed, scales = jax.jit(quant4_pack_pallas)(x)
    p_ref, s_ref, _ = ref.quant4_pack_ref(x)
    checks.true("quant4_pack",
                bool((np.asarray(packed) == np.asarray(p_ref)).all()),
                f"{int((np.asarray(packed) != np.asarray(p_ref)).sum())} "
                f"of {packed.size} bytes differ from the ref (bound 0)")
    checks.bound("quant4_scales", _tol_ratio(scales, s_ref, 1e-6, 0.0), 1.0,
                 "(|err|/rtol|ref|) ")
    back = jax.jit(quant4_unpack_pallas, static_argnums=2)(packed, scales,
                                                           x.shape[0])
    checks.bound("quant4_unpack",
                 float(jnp.max(jnp.abs(back - ref.quant4_roundtrip_ref(x)))),
                 1e-6)

    # the tiled matmul at a PowerSGD projection: (2048, 8192) @ (8192, 64).
    # A contraction this long outgrows the tests' fixed 1e-4 tolerance
    # (K <= 384 there); it is held to the fused kernels' ulp bound instead
    a, b = normal(8, (2048, 8192)), normal(9, (8192, 64))
    out = jax.jit(matmul_pallas)(a, b)
    with highest():
        want = jax.jit(ref.matmul_ref)(a, b)
    ulps = ULP_K * np.finfo(np.float32).eps * (np.abs(np.asarray(a))
                                               @ np.abs(np.asarray(b)))
    checks.bound("lowrank_mm", float(np.max(np.abs(np.asarray(out)
                                                    - np.asarray(want))
                                             / ulps)), 1.0,
                 "(|err|/ulp bound) ")

    for i, (m, n, r) in enumerate(((2048, 8192, 64), (2048, 6144, 512))):
        _check_fused(checks, m, n, r, normal(10 + 3 * i, (m, n)) * 0.3,
                     normal(11 + 3 * i, (m, n)) * 0.05,
                     normal(12 + 3 * i, (n, r)))


def _check_fused(checks: Checks, m, n, r, d, e, q) -> None:
    """The fused outer-step compressor's contract (tests/test_kernels.py
    ``_assert_fused_contract``) at one parameter shape, rank r_t = r/2."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.fused_compress import (fused_compress_ef,
                                              fused_decompress)

    rt = r // 2
    tag = f"fused_compress_ef({m}x{n},r={r})"
    hat, e_new, q_new, pay = jax.jit(fused_compress_ef)(d, e, q,
                                                        jnp.int32(rt))
    f32 = lambda x: np.asarray(x, np.float32)
    pP, sP, _ = ref.quant4_pack_ref(pay.p_factor.reshape(-1))
    pQ, sQ, _ = ref.quant4_pack_ref(pay.q_factor.reshape(-1))
    diff = int((np.asarray(pay.packed_p) != np.asarray(pP)).sum()
               + (np.asarray(pay.packed_q) != np.asarray(pQ)).sum())
    checks.true(f"{tag} wire bytes", diff == 0,
                f"{diff} packed bytes differ from the ref packer (bound 0)")
    checks.bound(f"{tag} scales",
                 max(_tol_ratio(pay.scales_p, sP, 1e-6, 0.0),
                     _tol_ratio(pay.scales_q, sQ, 1e-6, 0.0)), 1.0,
                 "(|err|/rtol|ref|) ")
    # reconstruction and EF residual against the payload's own oracle
    Pq = f32(ref.quant4_unpack_ref(pay.packed_p, pay.scales_p, m * r)
             ).reshape(m, r)
    Qq = f32(ref.quant4_unpack_ref(pay.packed_q, pay.scales_q, n * r)
             ).reshape(n, r)
    oracle = Pq @ Qq.T
    bound = ULP_K * np.finfo(np.float32).eps * (np.abs(Pq) @ np.abs(Qq).T)
    checks.bound(f"{tag} recon",
                 float(np.max(np.abs(f32(hat) - oracle) / (bound + 1e-30))),
                 1.0, "(|err|/ulp bound) ")
    M = f32(d) + f32(e)
    ef_bound = bound + 2e-6 * np.abs(M) + 1e-6
    checks.bound(f"{tag} error feedback",
                 float(np.max(np.abs(f32(e_new) - (M - oracle)) / ef_bound)),
                 1.0, "(|err|/bound) ")
    masked = float(np.abs(f32(pay.q_factor)[:, rt:]).max()
                   + np.abs(f32(q_new)[:, rt:]).max())
    checks.bound(f"{tag} rank mask", masked, 0.0)
    dec = jax.jit(fused_decompress, static_argnums=(4, 5, 6))(
        pay.packed_p, pay.scales_p, pay.packed_q, pay.scales_q, m, n, r)
    checks.bound(f"fused_decompress({m}x{n},r={r})",
                 float(np.max(np.abs(f32(dec) - f32(hat)))), 0.0)


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def check_serve(checks: Checks, cfg, requests: int = 8, prompt_len: int = 128,
                gen_len: int = 32) -> dict:
    """Serve ``cfg`` through the paged engine with the compiled kernel, then
    hold the engine's logits at every position to the dense model's."""
    from repro.launch import serve

    args = serve.build_parser().parse_args([
        "--arch", cfg.name, "--paged", "--backend", "pallas",
        "--batch", str(requests), "--prompt-len", str(prompt_len),
        "--gen-len", str(gen_len), "--eos", "-1"])
    st = serve.run(args, cfg)
    n_tok = [len(g) for g in st["generated"]]
    checks.true("serve requests", st["requests_done"] == requests
                and n_tok == [gen_len] * requests,
                f"{st['requests_done']} requests done, new tokens {n_tok}")
    err = serve_logit_error(cfg, st["prompts"], gen_len, args.page_size,
                            args.backend)
    checks.bound("serve logits vs dense forward", err, SERVE_LOGIT_TOL,
                 f"({requests} requests x {prompt_len + gen_len - 1} "
                 f"positions; |err|/std of the logits) ")
    return st


def serve_logit_error(cfg, prompts, gen_len: int, page_size: int,
                      backend: str) -> float:
    """Serve ``prompts`` through a ``ServeEngine`` that keeps its logits and
    return max |engine - dense| / std(dense) over every fed position: the
    paged step and the dense teacher-forced forward (XLA attention) over
    the engine's own sequences, both at f32 matmul precision."""
    import jax
    import numpy as np

    from repro.models import model as M
    from repro.serve.engine import ServeEngine

    M.set_activation_sharder(None)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    pages_per_seq = -(-(len(prompts[0]) + gen_len) // page_size)
    with jax.default_matmul_precision("highest"):
        eng = ServeEngine(params, cfg, max_seqs=len(prompts),
                          page_size=page_size,
                          n_pages=len(prompts) * pages_per_seq,
                          max_pages_per_seq=pages_per_seq, backend=backend,
                          eos_id=None, keep_logits=True)
        for p in prompts:
            eng.submit(p, gen_len)
        eng.run()
        done = sorted(eng.sched.done, key=lambda r: r.rid)
        seqs = np.asarray([r.prompt + r.generated for r in done], np.int32)
        dense = jax.jit(lambda p, t: M.logits_fn(
            p, cfg, M.forward_hidden(p, cfg, {"tokens": t})[0]))
        want = np.asarray(dense(params, seqs[:, :-1]), np.float64)
    del params, eng.params, eng.caches
    got = np.asarray([[eng.logits[r.rid][t] for t in range(want.shape[1])]
                      for r in done], np.float64)
    return float(np.abs(got - want).max() / want.std())


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def _train_args(cfg, *extra):
    from repro.launch import train
    return train.build_parser().parse_args([
        "--arch", cfg.name, "--rounds", "4", "--h-steps", "2",
        "--seq-len", str(SEQ_LEN), "--rank", str(RANK),
        "--inner-lr", str(INNER_LR), *extra])


def _batch_loss(cfg, params, shards, batch: int) -> float:
    """Mean over data shards of ``loss_fn`` on each shard's first batch.
    Shards 0..C-1 are the clusters' own streams: their first batches are
    what the drivers' first inner step sees."""
    import jax
    import numpy as np

    from repro.data.synthetic import SyntheticLM
    from repro.models import model as M

    M.set_activation_sharder(None)      # plain single-device reference
    # jit of the module function with cfg static: calls at one shape share
    # one executable
    loss = jax.jit(M.loss_fn, static_argnums=1)
    return float(np.mean([
        loss(params, cfg, SyntheticLM(cfg.vocab_size, SEQ_LEN, batch, seed=0,
                                      data_shard=c).next_batch())[0]
        for c in shards]))


def check_train(checks: Checks, cfg) -> dict:
    import jax
    import numpy as np

    from repro.launch import train
    from repro.models import model as M

    res = train.run(_train_args(cfg, "--global-batch", str(TRAIN_BATCH)),
                    cfg)
    final = jax.tree.map(lambda x: x[0], res.pop("state"))
    _report_train(res)
    losses = res["step_losses"]
    init = M.init_params(cfg, jax.random.PRNGKey(0))
    ref = _batch_loss(cfg, init, [0], TRAIN_BATCH)
    checks.bound("train first loss vs loss_fn",
                 abs(losses[0] - ref) / abs(ref), TRAIN_RTOL,
                 f"({losses[0]:.6f} vs {ref:.6f}; relative) ")
    checks.true("train losses finite", bool(np.isfinite(losses).all()),
                f"{len(losses)} inner steps")
    # each step sees a new batch, whose own spread hides a few rounds'
    # progress: the fall is read on held-out batches (data shards no
    # cluster trains on), at the initial and at the final outer params
    held_out = [HELD_OUT_SHARD + i for i in range(HELD_OUT_BATCHES)]
    before = _batch_loss(cfg, init, held_out, TRAIN_BATCH)
    after = _batch_loss(cfg, final, held_out, TRAIN_BATCH)
    checks.true("train held-out loss falls", after < before,
                f"{before:.6f} -> {after:.6f} over "
                f"{len(res['round_losses'])} rounds")
    return res


def _report_train(res: dict) -> None:
    fmt = lambda xs: " ".join(f"{x:.4f}" for x in xs)
    _say(f"  compile_s: " + " ".join(f"{k}={v:.1f}"
                                     for k, v in res["compile_s"].items()))
    for name, mem in res["memory"].items():
        _say(f"  memory_analysis {name}: " + " ".join(
            f"{k}={v / 2 ** 30:.3f}GiB" for k, v in mem.items()))
    _say(f"  inner_step_s: {fmt(res['inner_step_s'])}")
    _say(f"  outer_step_s: {fmt(res['outer_step_s'])}")
    _say(f"  step_losses: {fmt(res['step_losses'])}")
    _say(f"  round_losses: {fmt(res['round_losses'])}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def _placement(params) -> None:
    """Where each shard of a few cluster-stacked leaves lives."""
    import jax
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    for path, x in [f for f in flat if f[1].ndim >= 3][:3]:
        shards = " ".join(f"d{s.device.id}:{tuple(s.data.shape)}"
                          for s in x.addressable_shards)
        _say(f"  {jax.tree_util.keystr(path)} {tuple(x.shape)} "
             f"{x.sharding.spec}: {shards}")


def _held_out_loss(cfg, params) -> float:
    """``_batch_loss`` on the held-out shards, on one chip."""
    import jax
    return _batch_loss(cfg, jax.device_put(params, jax.devices()[0]),
                       [HELD_OUT_SHARD + i for i in range(HELD_OUT_BATCHES)],
                       TRAIN_BATCH)


def _sharded_run(tag: str, name: str, cfg, *extra, to_seq=None) -> dict:
    """``train.run`` on 2 clusters for ``MESH_ROUNDS`` rounds; the result
    keeps the held-out loss at cluster 0's final params (in the
    unpipelined layout when ``to_seq`` converts to it)."""
    import jax

    from repro.launch import train

    _say(f"[{tag}] {name}")
    res = train.run(_train_args(
        cfg, "--global-batch", str(2 * TRAIN_BATCH), "--clusters", "2",
        "--rounds", str(MESH_ROUNDS), *extra), cfg)
    state = res.pop("state")
    _placement(state)
    _report_train(res)
    row = jax.tree.map(lambda x: x[0], state)
    del state
    res["held_out"] = _held_out_loss(cfg, to_seq(row) if to_seq else row)
    return res


def _compare_runs(checks: Checks, tag: str, runs: dict, before: float
                  ) -> None:
    """Two runs of one DiLoCoX schedule on different meshes: per-round
    losses agree, and on each the held-out loss at the final params, which
    only the outer steps move, is below its value at ``before``, the
    initial params.  The two final held-out losses are printed, not held
    to each other: at the chip's default matmul precision the meshes'
    outer steps drift apart after the second exchange (PERF.md)."""
    (na, a), (nb, b) = runs.items()
    rel = max(abs(x - y) / abs(y)
              for x, y in zip(a["round_losses"], b["round_losses"]))
    checks.bound(f"{tag} per-round losses", rel, MESH_RTOL,
                 f"({na} against {nb}; relative) ")
    for name, r in runs.items():
        checks.true(f"{tag} {name} held-out loss falls",
                    r["held_out"] < before,
                    f"{before:.6f} at init -> {r['held_out']:.6f}")


def check_mesh(checks: Checks, cfg) -> None:
    """2 clusters x 2 model ranks against 2 clusters x 1 model rank."""
    import jax

    from repro.models import model as M

    runs = {f"clusters=2 model={m}": _sharded_run(
        "mesh", f"clusters=2 data=1 model={m}", cfg, "--model", str(m))
        for m in (2, 1)}
    init = _held_out_loss(cfg, M.init_params(cfg, jax.random.PRNGKey(0)))
    _compare_runs(checks, "mesh", runs, init)


def check_pp(checks: Checks, cfg) -> None:
    """``--inner pp`` on 2 clusters x 2 stages: its first-step loss against
    the unpipelined loss_fn, and its rounds against 2 clusters x 1 stage
    (both draw the same initial weights; only their layout differs)."""
    import jax

    from repro.parallel import pipeline as PP

    to_seq = lambda p: PP.sequential_params(p, cfg)
    runs = {f"stages={s}": _sharded_run(
        "pp", f"clusters=2 data=1 stages={s}", cfg, "--inner", "pp",
        "--pp-stages", str(s), "--pp-micro", str(TRAIN_BATCH), to_seq=to_seq)
        for s in (2, 1)}
    # the drivers' initial params (``init_train_state`` draws them so)
    init = to_seq(PP.init_pp_params(cfg, jax.random.PRNGKey(0),
                                    PP.PipelineConfig(n_stages=2)))
    ref = _batch_loss(cfg, init, [0, 1], TRAIN_BATCH)
    got = runs["stages=2"]["step_losses"][0]
    checks.bound("pp first loss vs loss_fn", abs(got - ref) / abs(ref),
                 PP_RTOL, f"({got:.6f} vs {ref:.6f}; relative) ")
    _compare_runs(checks, "pp", runs, _held_out_loss(cfg, init))

# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh and pipeline checks")
    opts = ap.parse_args(argv)

    sys.path.insert(0, _src_path())
    try:
        from repro import compile_cache
    except ImportError:
        print(f"chip_smoke.py needs the repository's src/ next to it "
              f"({_src_path()} has no repro package)", file=sys.stderr)
        return 2
    cache = compile_cache.enable()
    n_cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0

    import jax
    devs = jax.devices()
    want = 4 if opts.four_chips else 1
    if devs[0].platform != "tpu" or len(devs) < want:
        print(f"chip_smoke.py needs {want} TPU chip(s); jax sees "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 1
    if opts.four_chips and len(devs) != 4:
        print(f"--four-chips needs exactly 4 chips, jax sees {len(devs)}",
              file=sys.stderr)
        return 1
    if not opts.four_chips:
        devs = devs[:1]
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices())}
    _say(f"device: {device['kind']} x {device['count']} "
         f"({device['platform']}, jax {jax.__version__}); compile cache "
         f"{cache} ({n_cached} entries at start)")
    if os.environ.get("REPRO_USE_PALLAS"):
        print("unset REPRO_USE_PALLAS: training differentiates through XLA "
              "attention (the flash kernel is forward-only)", file=sys.stderr)
        return 1

    from repro.configs.base import get_config
    full = get_config("opt-1.3b")
    cut = dataclasses.replace(full, n_layers=TRAIN_LAYERS)
    checks = Checks()
    if opts.four_chips:
        phases = [("mesh", check_mesh, cut), ("pp", check_pp, cut)]
    else:
        phases = [("kernels", lambda c, _: check_kernels(c), None),
                  ("serve", check_serve, full), ("train", check_train, cut)]
    for name, fn, cfg in phases:
        if cfg is None:
            _say(f"[{name}]")
        else:
            depth = ("full depth" if cfg.n_layers == full.n_layers else
                     f"reduced: n_layers {full.n_layers}->{cfg.n_layers}")
            _say(f"[{name}] {cfg.name}: d_model {cfg.d_model}, "
                 f"{cfg.n_heads}x{cfg.resolved_head_dim} heads, d_ff "
                 f"{cfg.d_ff}, vocab {cfg.vocab_size}, seq {SEQ_LEN}; "
                 f"{depth}")
        if name == "train":
            _say("  attention: XLA (the flash kernel is forward-only)")
        t = time.perf_counter()
        fn(checks, cfg)
        _say(f"[{name}] done in {time.perf_counter() - t:.1f}s; "
             f"peak_bytes_in_use so far: "
             + " ".join(f"d{d.id}={_peak_gib(d):.3f}GiB" for d in devs))
    if checks.failed:
        print(f"chip_smoke.py: {len(checks.failed)} check(s) failed: "
              + ", ".join(checks.failed), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
