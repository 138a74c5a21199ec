"""Trace reduction: operation groups from HLO text, busy union, idle
share and gaps, on a trace recorded on a TPU v5e and on synthetic cases."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")

# HLO texts of one PageRank step's operations as a v5e trace names them
# (shapes of an undirected GAP kron graph at scale 21, cut after the
# operands)
GATHER = ("%fusion.29 = f32[89227264]{0:T(1024)} fusion(f32[2097152]"
          "{0:T(1024)S(1)} %get-tuple-element.272, s32[89227264]{0:T(1024)} "
          "%broadcast_clamp_fusion.3), kind=kCustom, "
          "calls=%fused_computation.clone.clone.clone")
PHASE2 = ("%fusion.30 = f32[38631424]{0:T(1024)} fusion(s32[89227264]"
          "{0:T(1024)} %get-tuple-element.273, f32[89227264]{0:T(1024)} "
          "%get-tuple-element.274, f32[]{:T(128)} %constant.182..sunk), "
          "kind=kCustom, calls=%fused_computation.10.clone.clone.clone")
PHASE3 = ("%fusion.31 = f32[2097153]{0:T(1024)S(1)} fusion(s32[38631424]"
          "{0:T(1024)} %get-tuple-element.271, f32[38631424]{0:T(1024)} "
          "%fusion.30, f32[]{:T(128)} %constant.182..sunk), kind=kCustom, "
          "calls=%fused_computation.17.clone.clone.clone")
SORT = ("%sort.3 = (s32[89227264]{0:T(1024)}, f32[89227264]{0:T(1024)}) "
        "sort(s32[89227264]{0:T(1024)} %reshape.111, f32[89227264]"
        "{0:T(1024)} %select_select_fusion.3), dimensions={0}, "
        "to_apply=%compare")
WHILE = ("%while.13 = (f32[2097152]{0:T(1024)}, f32[]{:T(128)}, s32[]"
         "{:T(128)}) while((f32[2097152]{0:T(1024)}, f32[]{:T(128)}) "
         "%tuple.52), condition=%wide.region_6.13, body=%wide.region_0.12")
LOOP = ("%compare_select_fusion.4 = s32[256,348544,1]{1,0,2:T(8,128)} "
        "fusion(s32[256,348544]{1,0:T(8,128)} %p0), kind=kLoop, "
        "calls=%fused_computation.4")


@pytest.mark.parametrize("text,group", [
    (GATHER, "fusion:kCustom"), (PHASE2, "scatter"), (PHASE3, "scatter"),
    (SORT, "sort"), (WHILE, "while"), (LOOP, "fusion:kLoop"),
    ("%scatter.2 = f32[8]{0} scatter(f32[8]{0} %a, s32[16,1]{1,0} %i, "
     "f32[16]{0} %u), to_apply=%add", "scatter"),
    ("%copy-start.4 = (pred[256,348544]{1,0}, pred[256,348544]{1,0}, u32[]) "
     "copy-start(pred[256,348544]{1,0} %p)", "copy-start"),
    ("%reshape.81 = pred[89227264]{0:T(1024)(128)(4,1)} reshape("
     "pred[256,348544]{1,0:T(8,128)(4,1)} %copy-done.4)", "reshape"),
])
def test_op_group_from_hlo_text(text, group):
    assert trace.op_group(text) == group


def test_recorded_tpu_trace_reduces_consistently():
    with open(os.path.join(DATA, "pagerank_small_trace.json")) as f:
        ev = trace.Events.from_json(json.load(f))
    s = trace.reduce(ev, "bench.solve")
    solve = [h for h in ev.host_spans if h[0] == "bench.solve"][0]
    assert s.window_s == pytest.approx(solve[2] / 1e9)
    # one core's leaf operations do not overlap: busy is their sum
    assert s.busy_s == pytest.approx(sum(s.by_group.values()), rel=1e-9)
    assert 0 < s.busy_s < s.window_s
    assert s.window_s - s.busy_s == pytest.approx(
        sum(g for _, g in s.gaps), rel=1e-6)
    assert "while" not in s.by_group  # it encloses its body
    # the slab engine's two scatters and its gather take the time
    assert s.share("scatter") > 0.5
    assert s.share("scatter") + s.share("fusion:kCustom") > 0.95
    b = s.breakdown()
    assert b["device_ops"][0][0] == "scatter"
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_reduce_clips_nests_and_names_gaps():
    ev = trace.Events(
        device_ops=[(0, "while", 0, 100), (0, "scatter", 10, 30),
                    (0, "fusion:kCustom", 50, 30), (0, "sort", 95, 20),
                    (1, "scatter", 10, 90)],
        host_spans=[("bench.window", 5, 100), ("bench.solve", 5, 60),
                    ("bench.solve", 65, 40), ("dispatch", 40, 10)])
    s = trace.reduce(ev, "bench.window")
    assert s.window_s == pytest.approx(100e-9)
    # chip 0: [10,40) [50,80) [95,105) = 70; chip 1: [10,100) = 90
    assert s.busy_s == pytest.approx(80e-9)
    assert s.by_group == pytest.approx(
        {"scatter": 120e-9, "fusion:kCustom": 30e-9, "sort": 10e-9})
    assert s.gaps[0] == ("bench.solve", pytest.approx(15e-9))  # [80, 95)
    assert ("dispatch", pytest.approx(10e-9)) in s.gaps  # [40, 50)
    assert sum(g for _, g in s.gaps) == pytest.approx(2 * 100e-9 - 160e-9)


def test_no_window_span_is_an_error():
    with pytest.raises(RuntimeError, match="bench.window"):
        trace.reduce(trace.Events([], [("other", 0, 1)]), "bench.window")


def test_load_reads_a_trace_without_device_ops(tmp_path):
    x = jnp.ones((64, 64))
    f = jax.jit(lambda a: a @ a)
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            f(x).block_until_ready()
    ev = trace.load(str(tmp_path))
    assert ev.device_ops == []  # the CPU has no TPU plane
    s = trace.reduce(ev, "bench.window")
    assert s.busy_s == 0 and s.share("scatter") is None
