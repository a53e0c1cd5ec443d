"""The ``train_rounds_mesh`` runner at a size the CPU runs: its reference,
spread over four host devices, reads as on one and as ``train_rounds``'s;
its collective reduction and the two readers on a trace-shaped stand-in."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

import run as harness_run
from harness import cell as cells
from harness import tracing
from harness import train_rounds_mesh as R

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "phi3-medium-14b-l1.train-2x2"

SCRIPT = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]
    import jax, numpy as np
    from harness import cell as cells, train_rounds as T
    from harness import train_rounds_mesh as R
    real = cells.load(CELL)
    model = dict(real.model, d_model=64, n_heads=8, n_kv_heads=2,
                 head_dim=16, d_ff=160, vocab_size=256)
    traffic = dict(real.traffic, seq_len=32, rank=8, h=3, batch_pool=8)
    cell = cells.Cell(name=CELL, chips=4, config={"model": model},
                      traffic=traffic, limits={}, end_to_end=[], per_layer=[])
    key, _ = cells.seed_streams(2 ** 31 + 3)
    gap = lambda a, b: {k: float(np.max(np.abs(np.subtract(a[k], b[k]))
                                        / np.maximum(np.abs(b[k]), 1e-30)))
                        for k in b}
    devs = jax.devices()
    out = {}
    # (the bfloat16 control rounds sums taken in another order to other
    # codes, and so reads differently on another placement by design)
    for kw in ({}, {"half_batch": True}):
        # two clusters: over four devices and on one
        out[json.dumps(kw)] = gap(
            R.reference_rounds(cell, key, devs[:4], 2, **kw),
            R.reference_rounds(cell, key, devs[:1], 2, **kw))
        # one cluster: against train_rounds's loop, which holds one
        # cluster only (it donates one zero tree as every cluster's)
        one = cells.Cell(name=CELL, chips=4, config=cell.config,
                         traffic=dict(traffic, clusters=1), limits={},
                         end_to_end=[], per_layer=[])
        out["one cluster " + json.dumps(kw)] = gap(
            R.reference_rounds(one, key, devs[:4], 2, **kw),
            T.reference_rounds(one, key, 2, **kw))
    print("GAPS " + json.dumps(out))
""")


def test_placed_reference_reads_as_the_one_chip_reference():
    env = dict(os.environ)
    r = subprocess.run(
        [sys.executable, "-c",
         f"BENCH = {BENCH!r}\nCELL = {CELL!r}\n" + SCRIPT],
        env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    line = [x for x in r.stdout.splitlines() if x.startswith("GAPS ")][-1]
    # the same mathematics; only the order of float32 sums differs
    for kw, gaps in json.loads(line[len("GAPS "):]).items():
        assert max(gaps.values()) < 1e-4, (kw, gaps)


def _op(name):
    return f"%{name} = f32[8]{{0}} " + {
        "all-reduce.1": "all-reduce(%a), replica_groups={{0,2},{1,3}}",
        "all-reduce.2": "all-reduce(%a), replica_groups=[2,2]<=[4]",
        "fusion.7": "fusion(%b), kind=kCustom, calls=%all-reduce-scatter.2",
        "async-collective-done.3": "fusion(%c), kind=kCustom, "
                                   "calls=%fused_computation.9",
        "fusion.1": "fusion(%all-gather.202), kind=kLoop, calls=%f.1",
        "all-gather-start.4": "all-gather-start(%d), dimensions={0}",
        "all-reduce-start.5": "all-reduce-start(%a), "
                              "replica_groups=[2,2]<=[2,2]T(1,0)",
        "all-reduce-done.5": "all-reduce-done(f32[8]{0} "
                             "%all-reduce-start.5)",
    }[name]


def test_collectives_split_by_program_per_call_and_chip():
    ms = 1_000_000
    step, outer = "jit_train_step(11)", "jit_outer_step(12)"
    chip = {"XLA Modules": [(0, 10 * ms, step), (10 * ms, 20 * ms, step),
                            (20 * ms, 40 * ms, outer), (50 * ms, 60 * ms,
                                                        step)],
            "XLA Ops": [(1 * ms, 3 * ms, _op("all-reduce.1")),
                        (3 * ms, 4 * ms, _op("fusion.1")),
                        (11 * ms, 12 * ms, _op("fusion.7")),
                        (12 * ms, 13 * ms, _op("all-gather-start.4")),
                        (21 * ms, 25 * ms, _op("async-collective-done.3")),
                        # a loop that holds a collective: only the leaf
                        (25 * ms, 35 * ms, "%while.2 = f32[8] while(%e)"),
                        (26 * ms, 27 * ms, _op("all-reduce.1")),
                        (27 * ms, 28 * ms, _op("all-reduce.2")),
                        (28 * ms, 29 * ms, _op("all-reduce-start.5")),
                        (30 * ms, 33 * ms, _op("all-reduce-done.5")),
                        (51 * ms, 52 * ms, _op("all-reduce.1"))]}
    other = {"XLA Modules": chip["XLA Modules"][:3],
             "XLA Ops": [(21 * ms, 31 * ms, _op("all-reduce.1"))]}
    got = R.collectives([chip, other], 0, 45 * ms, cluster_size=2)
    # inner step: (2 + 1 + 1) ms on the first chip over 2 calls, none on
    # the other's 2, of which the 2 ms of {0,2},{1,3} cross clusters;
    # outer: 10 ms and 10 ms over one call each, of which 1 + 1 + 3 (the
    # async pair across clusters) and 10 cross ({0,1},{2,3} does not)
    assert got["jit_train_step"] == pytest.approx((4e-3 / 4, 2e-3 / 4))
    assert got["jit_outer_step"] == pytest.approx((20e-3 / 2, 15e-3 / 2))
    assert set(got) == {"jit_train_step", "jit_outer_step"}


@pytest.mark.parametrize("attrs, across", [
    ("replica_groups={{0,2},{1,3}}", True),
    ("replica_groups={{0,1},{2,3}}", False),
    ("replica_groups=[2,2]<=[4]", False),
    ("replica_groups=[2,2]<=[2,2]T(1,0)", True),
    ("replica_groups=[1,4]<=[4]", True),
    ("source_target_pairs={{0,1},{1,0}}", False),
    ("source_target_pairs={{0,1},{1,2}}", True),
    ("dimensions={0}", False),
])
def test_crosses_reads_the_device_groups(attrs, across):
    assert R.crosses(attrs, cluster_size=2) is across


def test_exchange_bytes_counts_the_results_across_clusters():
    hlo = "\n".join([
        # combined: every array of the tuple
        "%all-reduce.31 = (f32[1,2048,256]{1,2,0:T(8,128)}, f32[512]{0}) "
        "all-reduce(%a, %b), replica_groups=[2,2]<=[2,2]T(1,0), "
        "to_apply=%add",
        # within a cluster: none
        "%all-reduce.47 = f32[1,5120,64]{1,2,0:T(8,128)} all-reduce(%c), "
        "replica_groups={{0,1},{2,3}}, to_apply=%add",
        # an async gather: its result, not its operand, and once
        "%all-gather-start.3 = (u8[327680]{0}, u8[655360]{0}) "
        "all-gather-start(%d), replica_groups={{0,2},{1,3}}, "
        "dimensions={0}",
        "%all-gather-done.3 = u8[655360]{0} all-gather-done(%all-gather-"
        "start.3)",
        # a permute's start: its result, not its context words
        "%collective-permute-start.1 = (bf16[8,64]{1,0}, bf16[8,64]{1,0}, "
        "u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%h), "
        "source_target_pairs={{0,2},{2,0}}"])
    assert R.exchange_bytes(hlo, cluster_size=2) == {
        "f32": (2048 * 256 + 512) * 4, "u8": 655360, "bf16": 8 * 64 * 2}


def test_exchange_readers_read_the_counters(monkeypatch):
    monkeypatch.setattr(harness_run.device, "describe", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4})
    cell = cells.load(CELL)
    cell.limits = {}
    trace = tracing.Trace(window_s=1.0, busy_s=0.99,
                          modules={"jit_train_step": [0.14] * 4,
                                   "jit_outer_step": [0.13] * 4},
                          ops={}, op_names={}, gaps=[])
    counters = {"tokens_per_s": 50000.0, "flops_per_token": 3.156e9,
                "outer_work": (1e11, 9.4e9),
                "collective_ms": {"jit_train_step": 20.0,
                                  "jit_outer_step": 30.0},
                "cross_cluster_ms": {"jit_train_step": 0.01,
                                     "jit_outer_step": 28.0}}
    ctx = harness_run.Context(devs=[None] * 4)
    res = ctx.result(cell, {}, attempted=1, failed=0, e2e={},
                     counters=counters, spans=tracing.Spans(), trace=trace,
                     memory=0)
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["train.inner_collective_ms"] == 20.0
    assert got["train.outer_exchange_ms"] == 28.0
    assert got["mfu.train"] == pytest.approx(
        100 * 50000 * 3.156e9 / (4 * 197e12))
    assert got["outer_step_roofline"] == pytest.approx(
        100 * 9.4e9 / 819e9 / 0.13)
    # without the counter (a run that traced nothing) they are left out
    del counters["collective_ms"], counters["cross_cluster_ms"]
    res = ctx.result(cell, {}, attempted=1, failed=0, e2e={},
                     counters=counters, spans=tracing.Spans(), trace=trace,
                     memory=0)
    assert not {"train.inner_collective_ms",
                "train.outer_exchange_ms"} & set(res["metrics"])


def test_the_cell_states_each_chip_its_share():
    cell = cells.load(CELL)
    assert cell.chips == 4
    tr = cell.traffic
    assert tr["clusters"] * tr["data"] * tr["model_ranks"] == cell.chips
    assert cell.config["reduced"] == ["n_layers"]
    assert {m["name"] for m in cell.per_layer} >= {
        "train.inner_collective_ms", "train.outer_exchange_ms",
        "outer_step_roofline", "mfu.train", "idle.train"}
