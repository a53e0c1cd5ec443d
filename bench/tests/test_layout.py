"""BENCHMARK.json against the files the harness finds by name."""
import json
import os
import re

import pytest

from harness import cell as cells
from harness import weights

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return cells.benchmark()


def test_names_units_and_directions(bench):
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in bench[k]}) == len(bench[k])
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])


def test_every_cell_finds_its_files(bench):
    for w in bench["workloads"]:
        c = cells.load(w["name"])
        assert c.chips in (1, 4)
        assert c.traffic["kind"] in ("train_rounds", "serve_closed_loop")
        assert set(c.limits) == {"train_rounds": {
            "loss_gap", "grad_gap", "move_gap", "outer_gap",
            "nonfinite_losses"},
            "serve_closed_loop": {"logit_gap"}}[c.traffic["kind"]]
        e2e = [m["name"] for m in c.end_to_end]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert c.per_layer


def test_configs_hold_what_the_program_runs(bench):
    import jax
    from repro.models import model as M
    for c in bench["configs"]:
        assert c["file"].startswith(bench["paths"][0] + "/")
        with open(os.path.join(cells.ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"]
        assert conf["reduced"] == c["reduced"]
        for k in conf["reduced"]:
            assert conf["model"][k] != conf["published"][k]
        cfg = cells.program_config(conf["model"], conf["arch"])
        weights.check_layout(conf["model"], jax.eval_shape(
            lambda: M.init_params(cfg, jax.random.PRNGKey(0))))


def test_per_layer_metrics_have_readers_and_move_a_reported_metric(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cell_names = {w["name"] for w in bench["workloads"]}
    layers = {}
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py"))
        moved = e2e[m["moves"]]
        for w in m["workloads"]:
            assert w in cell_names
            assert "workloads" not in moved or w in moved["workloads"]
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_traffic_and_limit_files_belong_to_a_cell(bench):
    traffic = {w["traffic"] for w in bench["workloads"]}
    cells_ = {w["name"] for w in bench["workloads"]}
    assert {f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic"))} \
        == traffic
    assert {f[:-5] for f in os.listdir(os.path.join(BENCH, "limits"))} \
        == cells_
