"""Cells of the benchmark's kinds at a size the CPU runs in seconds, held
to a real cell's limits, and driven as ``run.py`` drives them but past the
look for a chip."""
import json
import os
import time

from harness import cell as cells

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODEL = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
             d_ff=128, vocab_size=256, norm="layernorm", norm_eps=1e-6,
             mlp="gelu", rope_theta=10000.0, tie_embeddings=False,
             param_dtype="float32", compute_dtype="float32")
TRAIN = "opt-1.3b-l2.train-h16"
SERVE = "opt-1.3b.serve-chat"


def cell(name: str) -> cells.Cell:
    real = cells.load(name)
    tr = dict(real.traffic)
    if tr["kind"] == "train_rounds":
        tr.update(seq_len=32, rank=8, h=4, batch_pool=8, trace_rounds=1)
    else:
        tr.update(clients=4, pages=64, requests=4096, warmup_steps=5,
                  backend="ref", check_requests=12,
                  prompt={"median": 8, "sigma": 0.5, "min": 4, "max": 16},
                  answer={"median": 6, "sigma": 0.5, "min": 2, "max": 12})
    return cells.Cell(name=name, chips=1,
                      config={"arch": "opt-1.3b", "model": MODEL},
                      traffic=tr, limits=real.limits,
                      end_to_end=real.end_to_end, per_layer=[])


def run(c: cells.Cell, seed: int = 2 ** 31 + 99, seconds: float = 1.0):
    import importlib
    import jax
    import run as harness_run
    runner = importlib.import_module("harness." + c.traffic["kind"])
    ctx = harness_run.Context(jax.devices()[:1], time.perf_counter())
    return runner.run(c, seed, seconds, False, ctx)
