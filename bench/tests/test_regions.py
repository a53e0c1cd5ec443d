"""Device time by region and idle time by span (``harness/regions.py``):
each op's ``tf_op`` read from the trace's event metadata
(``harness/xspace.py``), the leaf ops' time by program and innermost
region, and the first device's idle time split across the host spans
over it."""
import gzip
import re

import jax
import pytest

from harness import regions, tracing, xspace
from test_trace import DATA

SCAN_OPS = ("dynamic_slice", "dynamic_update_slice")
# twelve serve-chat engine steps recorded on a TPU v5 lite after the
# program named its regions and the engine its spans (a `--trace 1` run)
NAMED = DATA.replace("serve_steps", "serve_chat_regions")
PAGED = r'custom-call\(s32\[\d+,\d+\].*custom_call_target="tpu_custom_call"'
ENGINE = ("bench.step", "admit", "plan", "device_step", "put", "dispatch",
          "fetch", "commit")


@pytest.fixture(scope="module")
def old():
    return regions.read(DATA, window="engine_step")


@pytest.fixture(scope="module")
def metadata():
    with gzip.open(DATA) as f:
        return xspace.event_metadata(f.read())["/device:TPU:0"]


@pytest.fixture(scope="module")
def named():
    return regions.read(NAMED)


def _last_op(path):
    return path.rstrip(":").rsplit("/", 1)[-1]


def test_op_metadata_carries_path_and_source(metadata):
    stats = {name: s for name, s in metadata}
    kernel = [s for name, s in metadata if name.startswith("%closed_call.13")]
    assert len(kernel) == 1
    assert kernel[0]["tf_op"] == \
        "jit(step)/while/body/closed_call/pallas_call:"
    assert kernel[0]["source"].endswith("serve/attention_paged.py:161")
    assert kernel[0]["hlo_category"]
    # a Pallas call's cost is not counted by the compiler
    assert kernel[0]["flops"] == kernel[0]["bytes_accessed"] == 0
    # the compiler's own casts and copies carry no path
    hoisted = [n for n in stats if n.startswith(("%convert.14", "%copy.85"))]
    assert hoisted and not any(stats[n].get("tf_op") for n in hoisted)


def test_layer_scan_slicing_of_the_kv_pool(old, metadata):
    # the step's layer scan slices each layer's weights and pages out of
    # the stacked arrays and writes the pool back: 23.3 ms of the window's
    # step, 93.4 ms over the recording's four steps
    window = sum(old.op_seconds[k] for k, p in old.op_paths.items()
                 if _last_op(p) in SCAN_OPS)
    assert window == pytest.approx(0.023345, rel=1e-3)
    with gzip.open(DATA) as f:
        pd = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    ops = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
           for p in pd.planes if p.name == "/device:TPU:0"
           for line in p.lines if line.name == "XLA Ops"
           for e in line.events]
    paths = {name: s.get("tf_op", "") for name, s in metadata}
    every = sum(b - a for a, b, n in tracing._leaves(ops)
                if _last_op(paths.get(n, "")) in SCAN_OPS) / 1e9
    assert every == pytest.approx(0.0934, rel=1e-3)


def test_regions_account_for_every_leaf_op(old):
    trace = tracing.reduce(DATA, window="engine_step")
    assert old.calls == {"jit_step": 1}
    assert sum(old.regions["jit_step"].values()) == \
        pytest.approx(sum(map(sum, trace.ops.values())), rel=1e-9)
    # recorded before the program named its regions: the ops with a path
    # name none; hoisted weight casts and whole-pool copies have no path
    assert set(old.regions["jit_step"]) == {regions.NO_REGION,
                                           regions.NO_METADATA}
    assert old.region_s([regions.NO_METADATA]) == pytest.approx(
        0.017058, rel=1e-3)
    assert old.region_s([regions.NO_METADATA], "jit_step") == \
        old.region_s([regions.NO_METADATA])
    assert old.region_s(["model.attn"]) == 0.0


@pytest.mark.parametrize("path, region", [
    ("", regions.NO_METADATA),
    ("jit(step)/while/body/closed_call/pallas_call:", regions.NO_REGION),
    ("jit(step)/decode.layers/while/body/dynamic_slice:", "decode.layers"),
    ("jit(train_step)/vmap(transpose(jvp()))/model.layers/while/body/"
     "closed_call/checkpoint/rematted_computation/model.attn/dot_general",
     "model.attn"),
    ("jit(outer_step)/outer.compress/vmap(outer.quant)/gather:",
     "outer.quant"),
    ("transpose(jvp(model.head))/mul", "model.head"),
    ("jit(s)/model.attn/le;jit(s)/vmap(jvp())/broadcast_in_dim",
     "model.attn"),
    ("jit(s)/vmap(jvp())/broadcast_in_dim;jit(s)/model.ffn/add",
     "model.ffn"),
])
def test_innermost_region_of_a_path(path, region):
    assert regions.region_of(path) == region


def test_idle_time_split_by_overlap_under_nested_spans():
    # device idle 0-100 and 200-300 ns; "inner" (50-250) nests in "outer"
    # (0-1000), "leaf" (260-280) in it; "other" lies between the gaps
    r = regions.Regions(regions={}, calls={}, op_paths={}, op_seconds={},
                        idle=[(0, 100), (200, 300)], runs=[(100, 200)],
                        host=[(0, 1000, "outer"), (50, 250, "inner"),
                              (260, 280, "leaf"), (120, 180, "other")])
    ns = lambda d: {k: pytest.approx(v * 1e-9) for k, v in d.items()}
    assert r.idle_by_span() == ns({"outer": 80, "inner": 100, "leaf": 20})
    assert r.idle_by_span({"outer"}) == ns({"outer": 200})
    assert r.idle_by_span({"inner", "leaf"}) == ns(
        {"none": 80, "inner": 100, "leaf": 20})
    assert r.idle_by_span(set()) == ns({"none": 200})
    # "other" launched the run at 120 but the device shows it from 100:
    # the host events move 20 ns back onto the device's clock, and "tail"
    # (90-110) then covers 20 ns of the first gap, not 10
    r.host.append((90, 110, "tail"))
    assert r.launch_lag_ns("other") == 20
    assert r.launch_lag_ns("outer") == 0
    assert r.idle_by_span({"tail"}) == ns({"none": 190, "tail": 10})
    assert r.idle_by_span({"tail"}, launch="other") == ns(
        {"none": 180, "tail": 20})


def test_idle_by_span_on_the_recorded_step(old):
    trace = tracing.reduce(DATA, window="engine_step")
    idle = trace.window_s - trace.busy_s
    every = old.idle_by_span()
    assert sum(every.values()) == pytest.approx(idle, rel=1e-6)
    # the longest wait is the host's fetch of the next tokens
    assert max(every, key=every.get) == "np.asarray(jax.Array)"
    # the window is the step's own span, so no span holds its idle time
    assert old.idle_by_span({"engine_step"}) == {
        "none": pytest.approx(idle, rel=1e-6)}


def test_recorded_steps_carry_the_program_regions(named):
    trace = tracing.reduce(NAMED)
    regs = named.regions["jit_step"]
    assert {"model.embed", "decode.layers", "model.attn", "decode.kv_write",
            "decode.paged_attention", "model.ffn", "model.head",
            regions.NO_METADATA} <= set(regs)
    assert sum(regs.values()) == \
        pytest.approx(sum(map(sum, trace.ops.values())), rel=1e-9)
    assert named.calls == {"jit_step": 12} and len(named.runs) == 12
    # the paged kernel is nearly all of its region
    kernel = trace.op_seconds(PAGED)[1]
    assert kernel <= named.region_s(["decode.paged_attention"]) < \
        1.001 * kernel
    # the scan's own slicing and write-back of the pool, ~35 ms a step
    assert 1e3 * regs["decode.layers"] / 12 == pytest.approx(34.77, rel=1e-3)


def test_the_kernel_reader_finds_the_named_kernel():
    # `paged_attention_roofline` finds the kernel by its first operand
    trace = tracing.reduce(NAMED)
    calls, _ = trace.op_seconds(PAGED)
    assert calls == 24 * 12
    kernel = [k for k in trace.ops if re.search(PAGED, trace.op_names[k])]
    assert kernel and all(k.startswith("%paged_attention") for k in kernel)


def test_recorded_idle_time_split_across_the_engine_spans(named):
    trace = tracing.reduce(NAMED)
    for launch in (None, "dispatch"):
        idle = named.idle_by_span(ENGINE, launch)
        assert set(idle) - {"none"} <= set(ENGINE)
        assert sum(idle.values()) == pytest.approx(
            trace.window_s - trace.busy_s, rel=1e-6)
        assert max(idle, key=idle.get) == "fetch"
    # on the host's clock a run starts 0.91 ms before its dispatch does;
    # moved onto the device's, the fetch holds 1.68 ms a step, not 2.59
    assert named.launch_lag_ns("dispatch") / 1e6 == pytest.approx(
        0.907, abs=1e-3)
    assert 1e3 * named.idle_by_span(ENGINE)["fetch"] / 12 == \
        pytest.approx(2.591, rel=1e-3)
    assert 1e3 * named.idle_by_span(ENGINE, "dispatch")["fetch"] / 12 == \
        pytest.approx(1.684, rel=1e-3)


def test_command_line(capsys):
    assert regions.main([NAMED, "--spans", ",".join(ENGINE),
                         "--launch", "dispatch"]) == 0
    out = capsys.readouterr().out
    assert "decode.paged_attention" in out and "fetch" in out
    assert regions.main([NAMED, "--window", "no such span"]) == 1
