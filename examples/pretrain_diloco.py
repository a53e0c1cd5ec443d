"""End-to-end driver: pre-train a ~100M-param dense model with the FULL
DiLoCoX stack (mesh runtime, adaptive compression, checkpointing) for a few
hundred steps.

  PYTHONPATH=src python examples/pretrain_diloco.py [--rounds 20]

The mesh is laid over every device jax sees, through the launcher's
device rule.  On the CPU the example asks XLA for 8 host devices (2 clusters x 2
data x 2 model); on one TPU chip it runs 1 x 1 x 1, on four 2 x 1 x 2.

This is the executable twin of the production dry-run: the same
launch/steps.py functions the 512-device dry-run lowers. NOTE: the full
default budget (20 rounds x 10 steps of a 116M model) is sized for an
accelerator; on a CPU use --rounds 2 --h-steps 2 to see the mechanics (CI
does).  Checkpoints go to examples/out/diloco_ckpt (git-ignored).
"""
import argparse
import os

# (clusters, data, model) for each device count the example supports
LAYOUTS = {1: (1, 1, 1), 4: (2, 1, 2), 8: (2, 2, 2)}
CPU_DEVICES = 8


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--h-steps", type=int, default=10)
    args = ap.parse_args()

    from repro.launch import mesh, train
    mesh.request_host_devices(CPU_DEVICES)      # CPU only; before jax init
    import jax
    n = jax.device_count()
    if n not in LAYOUTS:
        raise SystemExit(f"no mesh layout for {n} devices "
                         f"(supported: {sorted(LAYOUTS)})")
    clusters, data, model = LAYOUTS[n]
    ckpt = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out",
                        "diloco_ckpt")
    train.main([
        "--arch", "hundred-m", "--clusters", str(clusters),
        "--data", str(data), "--model", str(model),
        "--rounds", str(args.rounds), "--h-steps", str(args.h_steps),
        "--global-batch", "16", "--seq-len", "128",
        "--inner-lr", "1e-3", "--outer-lr", "0.5", "--outer-momentum", "0.7",
        "--rank", "32", "--adaptive", "--ckpt-dir", ckpt,
    ])


if __name__ == "__main__":
    main()
