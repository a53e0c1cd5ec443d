"""The per-layer readers, on the recorded chip trace and on a trace-shaped
stand-in, through the same assembly ``run.py`` uses."""
import types

import pytest

import run as harness_run
from harness import cell as cells
from harness import tracing
from harness import workcount as w
from test_trace import DATA

FULL = cells.load("opt-1.3b.serve-chat").model


def _spans(*records):
    spans = tracing.Spans()
    spans.records.extend(records)
    return spans


def _result(cell, trace, spans, counters):
    ctx = harness_run.Context(devs=[None])
    return ctx.result(cell, {}, attempted=1, failed=0, e2e={},
                      counters=counters, spans=spans, trace=trace, memory=0)


def test_serving_readers_on_the_recorded_step(monkeypatch):
    monkeypatch.setattr(harness_run.device, "describe", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    trace = tracing.reduce(DATA, window="engine_step")
    cell = cells.load("opt-1.3b.serve-chat")
    cell.limits = {}
    # the recorded step: 32 slots, each holding about 105 positions
    contexts = [[105] * 32]
    counters = {"window_s": trace.window_s,
                "flops": sum(w.decode_flops(FULL, c) for c in contexts[0]),
                "attn_bytes": w.paged_attention_bytes(FULL, contexts[0]),
                "attn_flops": w.paged_attention_flops(FULL, contexts[0])}
    # the engine's step inside the window's step, and one after the window
    # (the engine runs on to finish the requests that are checked)
    spans = _spans(("device_step", 0.0005, 0.1865),
                   ("bench.step", 0.0, 0.1871), ("device_step", 0.2, 0.5))
    res = _result(cell, trace, spans, counters)
    m = {k: v["value"] for k, v in res["metrics"].items()}
    assert m["serve.device_ms_per_step"] == pytest.approx(186.0)
    assert m["serve.host_ms_per_step"] == pytest.approx(1.1)
    # 24 layers x 32 slots x 105 positions x 16 KiB at 819 GB/s: 1.6 ms of
    # the kernel's 137 ms
    assert m["paged_attention_roofline"] == pytest.approx(
        100 * w.paged_attention_bytes(FULL, contexts[0]) / 819e9
        / 0.137211244)
    assert 0 < m["paged_attention_roofline"] < 2
    assert 0 < m["mfu.serve"] < 100
    assert m["idle.serve"] == pytest.approx(
        100 * (1 - trace.busy_s / trace.window_s))
    assert res["device"]["busy_s"] == trace.busy_s
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert list(res)[-1] == "checks"


def test_training_readers(monkeypatch):
    monkeypatch.setattr(harness_run.device, "describe", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    cell = cells.load("opt-1.3b-l2.train-h4")
    cell.limits = {}
    m = cell.model
    trace = tracing.Trace(window_s=1.0, busy_s=0.97,
                          modules={"jit_train_step": [0.1, 0.1],
                                   "jit_outer_step": [0.1]},
                          ops={}, op_names={}, gaps=[])
    work = w.outer_step_work(m, rank=64, clusters=1)
    counters = {"tokens_per_s": 30000.0,
                "flops_per_token": w.train_flops_per_token(m, 2048),
                "outer_work": work}
    res = _result(cell, trace, _spans(), counters)
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["train.inner_step_ms"] == pytest.approx(100.0)
    assert got["train.outer_step_ms"] == pytest.approx(100.0)
    assert got["outer_step_roofline"] == pytest.approx(
        100 * work[1] / 819e9 / 0.1)
    assert got["mfu.train"] == pytest.approx(
        100 * 30000 * w.train_flops_per_token(m, 2048) / 197e12)
    assert got["idle.train"] == pytest.approx(3.0)


def test_a_reader_with_nothing_to_read_is_left_out(monkeypatch):
    monkeypatch.setattr(harness_run.device, "describe", lambda: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    cell = cells.load("opt-1.3b-l2.train-h4")
    cell.limits = {}
    trace = tracing.Trace(window_s=1.0, busy_s=0.5, modules={}, ops={},
                          op_names={}, gaps=[])
    res = _result(cell, trace, _spans(), {"tokens_per_s": 0})
    assert set(res["metrics"]) == {"idle.train"}
