"""The trace reduction, on a trace recorded on a TPU v5 lite: four engine
steps of full-depth OPT-1.3B serving (32 slots, 96 pages per slot), each
inside a host span named ``engine_step``; the first is the window."""
import os

import pytest

from harness import tracing

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "serve_steps.xplane.pb.gz")
PAGED = r'custom-call\(s32\[\d+,\d+\].*custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def trace():
    return tracing.reduce(DATA, window="engine_step")


def test_window_and_busy_time(trace):
    # the step's one program ran 183.87 ms of the 187.11 ms span
    assert trace.window_s == pytest.approx(0.187109778)
    assert trace.modules == {"jit_step": [pytest.approx(0.183870232)]}
    assert trace.busy_s == pytest.approx(0.18387, rel=1e-3)
    assert trace.busy_s <= trace.window_s


def test_kernel_time_by_its_operands(trace):
    calls, seconds = trace.op_seconds(PAGED)
    assert calls == 24                      # one per layer
    assert seconds == pytest.approx(0.137211244)


def test_leaf_ops_exclude_the_loop_that_holds_them(trace):
    assert not any(k.startswith("%while") for k in trace.ops)
    assert sum(map(sum, trace.ops.values())) <= trace.busy_s * 1.0001


def test_breakdown(trace):
    b = trace.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0].startswith("%closed_call.13 = f32[32,32,64]")
    assert b["device_ops"][0][1] == pytest.approx(0.137211244)
    # the longest gap: the host waiting for the next tokens
    assert b["idle_gaps"][0] == ["np.asarray(jax.Array)",
                                 pytest.approx(0.002265862)]
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_no_window_no_trace():
    assert tracing.reduce(DATA, window="no such span") is None


def test_op_label():
    assert tracing.op_label(
        "%convert.14 = bf16[24,2048,8192]{2,1,0:T(8,128)(2,1)} convert("
        "f32[24,2048,8192]{2,1,0:T(8,128)} %p)") == \
        "%convert.14 = bf16[24,2048,8192] convert"
