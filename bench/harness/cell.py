"""Look up a cell of ``BENCHMARK.json`` and everything it names."""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

# The model numbers a configuration file states, as the program's
# ModelConfig names them.  ``mlp`` and ``norm_eps`` have no field there:
# the layout of the weights shows the first, and the second is checked by
# the comparison with the reference.
PROGRAM_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
                "d_ff", "vocab_size", "norm", "rope_theta", "tie_embeddings",
                "param_dtype", "compute_dtype")


def _load(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> Dict[str, Any]:
    return _load(os.path.join(root, "BENCHMARK.json"))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]       # configs/<config>.json
    traffic: Dict[str, Any]      # traffic/<traffic>.json
    limits: Dict[str, float]     # limits/<cell>.json
    end_to_end: list             # BENCHMARK.json metrics that apply here
    per_layer: list

    @property
    def model(self) -> Dict[str, Any]:
        return self.config["model"]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load(name: str, root: str = ROOT) -> Cell:
    bench = benchmark(root)
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; known: "
                         f"{sorted(entries)}")
    w = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load(os.path.join(root, configs[w["config"]]["file"]))
    bdir = os.path.join(root, bench["paths"][0])
    traffic = _load(os.path.join(bdir, "traffic", w["traffic"] + ".json"))
    limits = _load(os.path.join(bdir, "limits", name + ".json"))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def program_config(model: Dict[str, Any], arch: str):
    """The program's ModelConfig for ``arch`` with the file's numbers put
    in; refuses a file whose numbers the program cannot take."""
    from repro.configs.base import get_config
    base = get_config(arch)
    changes = {k: model[k] for k in PROGRAM_KEYS
               if k in model and getattr(base, k) != model[k]}
    cfg = dataclasses.replace(base, **changes)
    if cfg.resolved_head_dim != model["head_dim"]:
        raise SystemExit(f"{arch}: head_dim {cfg.resolved_head_dim} != "
                         f"{model['head_dim']}")
    return cfg


def seed_streams(seed: int):
    """(jax key, numpy Generator) drawn from ``seed``, which may be any
    whole number: both pass through numpy's SeedSequence."""
    import jax.numpy as jnp
    import numpy as np
    ss = np.random.SeedSequence(int(seed) % (2 ** 64))
    key = jnp.asarray(ss.generate_state(2, dtype=np.uint32))
    return key, np.random.default_rng(ss.spawn(1)[0])
