"""In-process serving example on the paged continuous-batching engine:
staggered mixed-length requests share one page pool, and the resident KV
footprint is compared against the dense per-slot-max-length layout.

  PYTHONPATH=src python examples/serve_decode.py [--arch granite-3-8b]
  PYTHONPATH=src python examples/serve_decode.py --dense   # legacy driver

Unsupported families (SSM/MLA/enc-dec) fall back to the dense driver
(``repro.launch.serve`` without ``--paged``), in this process.  Both run
on the first device jax sees: the CPU, or one TPU chip.
"""
import argparse


def _dense_fallback(arch: str) -> None:
    from repro.launch import serve
    serve.main(["--arch", arch, "--smoke", "--batch", "4",
                "--prompt-len", "12", "--gen-len", "12"])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b")
    ap.add_argument("--dense", action="store_true",
                    help="run the legacy dense driver instead")
    args = ap.parse_args()

    if args.dense:
        _dense_fallback(args.arch)
        return

    import jax
    import numpy as np

    from repro.configs.base import get_config
    from repro.models import model as M
    from repro.serve.engine import ServeEngine, supports_paged

    cfg = get_config(args.arch).reduced()
    ok, why = supports_paged(cfg)
    if not ok:
        print(f"{args.arch}: {why} -> dense driver")
        _dense_fallback(args.arch)
        return

    params = M.init_params(cfg, jax.random.PRNGKey(0))
    ps, max_pages, gen = 4, 4, 6
    engine = ServeEngine(params, cfg, max_seqs=3, page_size=ps,
                         n_pages=3 * max_pages, max_pages_per_seq=max_pages)

    # staggered arrivals, mixed prompt lengths — the continuous-batching
    # regime: slots recycle as short requests finish
    rng = np.random.default_rng(0)
    for r, (arrival, plen) in enumerate(
            [(0, 4), (0, 7), (1, 3), (3, 9), (5, 5), (6, 4)]):
        engine.submit(rng.integers(0, cfg.vocab_size, plen).tolist(), gen,
                      arrival=arrival)

    st = engine.run()
    for req in sorted(engine.sched.done, key=lambda r: r.rid):
        print(f"  req {req.rid}: arrive@{req.arrival} "
              f"admit@{req.admit_step} done@{req.done_step} "
              f"({req.finish_reason}) -> {req.generated}")
    print(f"{st['requests_done']} requests in {st['steps']} steps "
          f"(ttft p50 {st['ttft_steps_p50']:.0f} steps, "
          f"{st['decode_tok_per_step']:.2f} decode tok/step)")

    # paged-vs-dense resident KV: the pool holds peak_pages_used pages;
    # a dense cache holds max_seqs * s_max positions whether used or not
    pool, peak, dense = (st["kv_pool_bytes"], st["kv_peak_bytes"],
                         st["dense_equiv_bytes"])
    print(f"KV bytes: pool {pool} / peak resident {peak} "
          f"vs dense {dense} ({peak / dense:.0%} of dense)")
    print("SERVE-EXAMPLE-OK")


if __name__ == "__main__":
    main()
