"""Serving driver: dense greedy loop (legacy, every family) or the paged
continuous-batching engine (``--paged``; dense/moe GQA stacks), loading
DiLoCoX-trainer checkpoints via ``repro.checkpoint``.

  python -m repro.launch.serve --arch gemma3-1b --smoke \
      --batch 4 --prompt-len 16 --gen-len 16 [--paged] [--ckpt DIR|PATH]

The serving mesh is (data, model) over ``jax.devices()`` (default 1 x 1:
one chip, or the one CPU device); ``--devices`` (if given) must match the
device count, and on the CPU it asks XLA for that many fake host devices.
``run(args, cfg)`` serves an already-built config and returns the run's
statistics (``chip_smoke.py`` calls it at OPT-1.3B width).

Throughput is reported per phase — prefill tok/s (prompt tokens absorbed
into the cache) and decode tok/s (tokens actually generated) — plus the
combined line CI greps. EOS handling: generation stops early once every
sequence has emitted ``cfg.eos_id`` (override with ``--eos``, disable
with ``--eos -1``), and post-EOS positions are masked to the EOS id in
the sample output.
"""
import argparse
import contextlib
import os
import sys


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--devices", type=int, default=None,
                    help="expected device count (default: every device jax "
                         "sees); on the CPU, the number of fake host "
                         "devices to create")
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--ckpt", default="",
                    help="checkpoint path (or dir: latest) from "
                         "launch/train.py --ckpt-dir; both the unstacked "
                         "and cluster-stacked params layouts load")
    ap.add_argument("--eos", type=int, default=None,
                    help="EOS token id (default: cfg.eos_id; -1 disables)")
    g = ap.add_mutually_exclusive_group()
    g.add_argument("--paged", action="store_true",
                   help="serve on the paged continuous-batching engine")
    g.add_argument("--dense", action="store_true",
                   help="legacy fixed-batch dense loop (the default)")
    ap.add_argument("--requests", type=int, default=0,
                    help="paged: number of requests (default: --batch)")
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="paged: physical page pool size (default: "
                         "batch * pages-per-seq, i.e. dense-equivalent)")
    ap.add_argument("--policy", default="continuous",
                    choices=["continuous", "static"])
    ap.add_argument("--backend", default="ref", choices=["ref", "pallas"])
    ap.add_argument("--trace", default="",
                    help="write a Chrome-trace of the serve phases here")
    ap.add_argument("--metrics-out", default="",
                    help="write repro_serve_* metrics (Prometheus text)")
    ap.add_argument("--log-json", action="store_true")
    return ap


def _load_params(path, params_like, log):
    """Restore the ``{"params": ...}`` tree saved by launch/train.py.
    Accepts the pp path (unstacked) and the GSPMD path (cluster-stacked:
    every row is identical post-round, row 0 is taken)."""
    import json

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint import checkpoint as ckpt_lib

    if os.path.isdir(path):
        found = ckpt_lib.latest(path)
        if found is None:
            raise FileNotFoundError(f"no checkpoints under {path!r}")
        path = found
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_like)
    with np.load(path + ".npz") as data:
        leaves = []
        for p, ref in flat:
            key = "['params']" + jax.tree_util.keystr(p)
            arr = data[key]
            if arr.shape != tuple(ref.shape):
                if arr.shape[1:] == tuple(ref.shape):
                    arr = arr[0]          # cluster-stacked -> row 0
                else:
                    raise ValueError(f"{key}: checkpoint shape {arr.shape} "
                                     f"vs model {tuple(ref.shape)}")
            leaves.append(jnp.asarray(arr).astype(ref.dtype))
    with open(path + ".json") as f:
        step = json.load(f)["step"]
    log.info(f"restored params from {path} (round {step})")
    return jax.tree_util.tree_unflatten(treedef, leaves)


def main(argv=None) -> None:
    args = build_parser().parse_args(argv)
    from repro.launch.mesh import request_host_devices
    request_host_devices(args.devices)

    from repro import compile_cache
    from repro.configs.base import get_config
    compile_cache.enable()
    cfg = get_config(args.arch)
    st = run(args, cfg.reduced() if args.smoke else cfg)
    if st.get("unsupported"):
        sys.exit(2)


def run(args, cfg) -> dict:
    """Serve ``cfg`` as ``args`` describes and return the run's
    statistics (the paged engine's ``stats()`` plus ``"generated"``, each
    request's tokens in submission order, on the paged path)."""
    import jax
    import numpy as np

    from repro.launch.mesh import launcher_mesh
    from repro.models import model as M
    from repro.obs import (MetricsRegistry, Tracer, configure_logging,
                           get_logger)
    from repro.parallel import sharding as sh

    configure_logging(stream=sys.stdout,
                      json_stream=(sys.stderr if args.log_json else None))
    log = get_logger("launch.serve")
    tracer = Tracer("serve-driver") if args.trace else None
    if tracer is not None:
        span = tracer.span
    else:
        def span(name, **kw):
            return contextlib.nullcontext()
    metrics = MetricsRegistry() if args.metrics_out else None

    eos = cfg.eos_id if args.eos is None else (
        None if args.eos < 0 else args.eos)
    mesh = launcher_mesh((args.data, args.model), ("data", "model"),
                         args.devices)
    M.set_activation_sharder(sh.make_activation_sharder(mesh))

    params = M.init_params(cfg, jax.random.PRNGKey(0))
    if args.ckpt:
        params = _load_params(args.ckpt, params, log)

    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (max(args.batch, args.requests or 0),
                                 args.prompt_len), 0, cfg.vocab_size)

    if args.paged:
        st = _run_paged(args, cfg, params, np.asarray(prompt), eos, log,
                        span, metrics)
    else:
        st = _run_dense(args, cfg, params, prompt, eos, log, span, metrics)

    if tracer is not None:
        tracer.write(args.trace)
        log.info(f"wrote {args.trace}")
    if metrics is not None:
        metrics.write_prometheus(args.metrics_out)
        log.info(f"wrote {args.metrics_out}")
    return st


def _run_dense(args, cfg, params, prompt, eos, log, span, metrics) -> dict:
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import steps
    from repro.models import model as M

    B = args.batch
    prompt = prompt[:B]
    s_max = args.prompt_len + args.gen_len
    state = M.init_decode_state(cfg, B, s_max)
    if cfg.is_encdec:
        fe = jax.random.normal(jax.random.PRNGKey(7),
                               (B, cfg.n_frontend_tokens,
                                cfg.d_model)) * 0.02
        mem = M.prefill_encoder(params, cfg, fe)
        state = M.fill_cross_caches(params, cfg, state, mem)

    serve_step = jax.jit(steps.make_serve_step(cfg, eos_id=eos))
    finished = jnp.zeros((B,), bool)

    def call(tokens):
        nonlocal state, finished
        if eos is None:
            nxt, state = serve_step(params, state, tokens)
        else:
            nxt, state, finished = serve_step(params, state, tokens,
                                              finished)
        return nxt

    t0 = time.time()
    with span("prefill", tokens=B * args.prompt_len):
        for t in range(args.prompt_len):
            nxt = call(prompt[:, t:t + 1])
            if eos is not None and t < args.prompt_len - 1:
                finished = jnp.zeros((B,), bool)  # prompt-forced outputs
    t1 = time.time()
    outs = [nxt]
    with span("decode"):
        for t in range(args.gen_len - 1):
            if eos is not None and bool(finished.all()):
                log.info(f"all sequences hit EOS after {t + 1} tokens")
                break
            nxt = call(nxt)
            outs.append(nxt)
    gen = np.concatenate([np.asarray(o) for o in outs], axis=1)
    t2 = time.time()

    # prefill absorbs prompt tokens; decode generates gen.shape[1] tokens
    # per row, the first of which came out of the last prefill step
    prefill_toks = B * args.prompt_len
    decode_toks = B * (gen.shape[1] - 1)
    print(f"prefill: {prefill_toks / max(t1 - t0, 1e-9):.1f} tok/s "
          f"({prefill_toks} tokens, {t1 - t0:.2f}s)")
    print(f"decode: {decode_toks / max(t2 - t1, 1e-9):.1f} tok/s "
          f"({decode_toks} tokens, {t2 - t1:.2f}s)")
    print(f"generated shape {gen.shape}; "
          f"{(prefill_toks + decode_toks) / max(t2 - t0, 1e-9):.1f} tok/s "
          f"({t2 - t0:.2f}s total)")
    print("sample:", gen[0][:12].tolist())
    if metrics is not None:
        metrics.counter("repro_serve_prefill_tokens").inc(prefill_toks)
        metrics.counter("repro_serve_decode_tokens").inc(decode_toks)
    print("SERVE-DRIVER-OK")
    return {"prefill_tokens": prefill_toks, "decode_tokens": decode_toks,
            "wall_s": t2 - t0, "generated": gen}


def _run_paged(args, cfg, params, prompts, eos, log, span, metrics) -> dict:
    from repro.serve.engine import ServeEngine, supports_paged

    ok, why = supports_paged(cfg)
    if not ok:
        print(f"SERVE-DRIVER-UNSUPPORTED: {args.arch}: {why}")
        return {"unsupported": why}

    ps = args.page_size
    max_new = args.gen_len
    max_pages = -(-(args.prompt_len + max_new) // ps)
    n_pages = args.pool_pages or args.batch * max_pages
    engine = ServeEngine(params, cfg, max_seqs=args.batch, page_size=ps,
                         n_pages=n_pages, max_pages_per_seq=max_pages,
                         backend=args.backend, eos_id=eos,
                         policy=args.policy, metrics=metrics, span=span)
    n_req = args.requests or args.batch
    for r in range(n_req):
        engine.submit(prompts[r].tolist(), max_new, arrival=0)
    built = engine.compile()
    print(f"compiled paged decode step in {built['compile_s']:.1f}s")
    st = engine.run()
    st.update(built)

    print(f"paged engine: {st['requests_done']} requests in {st['steps']} "
          f"steps ({args.policy}, backend={args.backend}, "
          f"pool={n_pages}x{ps} pages)")
    print(f"prefill: {st['prefill_tok_s']:.1f} tok/s "
          f"({st['prefill_tokens']} tokens)")
    print(f"decode: {st['decode_tok_s']:.1f} tok/s "
          f"({st['decode_tokens']} tokens, "
          f"{st['decode_tok_per_step']:.2f} tok/step)")
    total = st["prefill_tokens"] + st["decode_tokens"]
    print(f"generated shape ({st['requests_done']}, {max_new}); "
          f"{total / max(st['wall_s'], 1e-9):.1f} tok/s "
          f"({st['wall_s']:.2f}s total)")
    print(f"ttft p50/p99: {st['ttft_steps_p50']:.0f}/"
          f"{st['ttft_steps_p99']:.0f} steps; per-token p50/p99: "
          f"{st['per_token_ms_p50']:.2f}/{st['per_token_ms_p99']:.2f} ms")
    print(f"kv bytes: pool {st['kv_pool_bytes']} (peak resident "
          f"{st['kv_peak_bytes']}) vs dense {st['dense_equiv_bytes']}")
    done = sorted(engine.sched.done, key=lambda r: r.rid)
    print("sample:", done[0].generated[:12] if done else [])
    print(f"admission fingerprint: {st['admission_fingerprint']}")
    print("SERVE-DRIVER-OK")
    st["generated"] = [r.generated for r in done]
    st["prompts"] = [r.prompt for r in done]
    return st


if __name__ == "__main__":
    main()
