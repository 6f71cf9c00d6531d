"""The trace reduction on synthetic traces, and on a small trace recorded
on a TPU v5e chip."""
import pathlib

import pytest

from chipbench import trace

DATA = pathlib.Path(__file__).parent / "data"


def test_merge_and_subtract():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == \
        [(0, 2), (3, 5), (7, 10)]
    assert trace.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert trace.subtract([(0, 4)], []) == [(0, 4)]


@pytest.mark.parametrize("text,kind", [
    ("%mec_conv_fused_pallas.34 = f32[32,109,109,64]{3,2,1,0:T(8,128)} "
     "custom-call(bf16[32,224,112,128]{3,2,1,0:T(8,128)(2,1)} %copy.9, "
     "bf16[7,4,128,64]{3,2,1,0:T(8,128)(2,1)S(1)} %bitcast.2), "
     'custom_call_target="tpu_custom_call"', "mosaic"),
    ("%convert_maximum_fusion = bf16[32,109,109,64]{3,2,1,0:T(8,128)(2,1)S(1)}"
     " fusion(f32[32,109,109,64]{3,2,1,0:T(8,128)} "
     "%mec_conv_fused_pallas.34), kind=kLoop, calls=%fused_computation",
     "other"),
    ("%copy-start = (bf16[7,7,64,64]{3,2,1,0:T(8,128)(2,1)S(1)}, "
     "bf16[7,7,64,64]{3,2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}) "
     "copy-start(bf16[7,7,64,64]{3,2,1,0:T(8,128)(2,1)} %params_0_.1)",
     "other"),
    ("%convolution.3 = f32[8,8]{1,0} convolution(f32[8,8]{1,0} %a, "
     "f32[8,8]{1,0} %b), window={size=1}", "conv"),
    ("%fusion.12 = bf16[7,7,64,64]{3,2,1,0} fusion(bf16[32,8]{1,0} %a), "
     "kind=kOutput, calls=%fused_computation.3", "conv"),
    ("%dot.4 = f32[8,8]{1,0} dot(f32[8,8]{1,0} %a, f32[8,8]{1,0} %b)", "dot"),
    ("%all-reduce.5 = f32[64]{0} all-reduce(f32[64]{0} %g), "
     "replica_groups={{0,1,2,3}}", "collective"),
    ("%all-reduce-start.1 = f32[64]{0} all-reduce-start(f32[64]{0} %g)",
     "collective"),
])
def test_classify(text, kind):
    assert trace.classify(text) == kind


def _ns(ms):
    return ms * 1e6


def test_summarize_synthetic():
    """Busy is the union of ops, idle gaps are labelled by the host span
    that covers them, collectives count as exposed only where no compute
    runs, and everything is clipped to the window."""
    op = lambda n, a, b, k: trace.Op(n, _ns(a), _ns(b), k)  # noqa: E731
    ops = [op("conv.0", -5, 10, "conv"),       # starts before the window
           op("mec.1", 5, 20, "mosaic"),       # overlaps conv.0
           op("ar.2", 25, 35, "collective"),   # 25-30 exposed
           op("fusion.3", 30, 40, "other"),
           op("fusion.3", 60, 70, "other"),
           op("late.4", 95, 120, "other")]     # ends after the window
    modules = [(_ns(0), _ns(40)), (_ns(60), _ns(70)), (_ns(200), _ns(210))]
    spans = [trace.Span(trace.WINDOW_SPAN, _ns(0), _ns(100)),
             trace.Span("bench.step", _ns(0), _ns(100)),
             trace.Span("bench.wait", _ns(40), _ns(60)),
             trace.Span("bench.request", _ns(70), _ns(95)),
             trace.Span("bench.plan", _ns(-50), _ns(-10))]
    s = trace.summarize({"/device:TPU:0": (ops, modules)}, spans)
    d = s.devices[0]
    assert s.window_s == pytest.approx(0.1)
    assert d.busy_s == pytest.approx(0.020 + 0.015 + 0.010 + 0.005)
    assert s.idle_share() == pytest.approx(0.5)
    assert d.kind_s["conv"] == pytest.approx(0.010)
    assert d.kind_s["mosaic"] == pytest.approx(0.015)
    assert d.kind_s["collective"] == pytest.approx(0.010)
    assert d.kind_s["other"] == pytest.approx(0.025)
    assert d.exposed_collective_s == pytest.approx(0.005)
    assert d.launches == 2
    assert d.op_s["fusion.3"] == pytest.approx(0.020)
    b = s.breakdown()
    assert b["device_ops"][0] == ["fusion.3", pytest.approx(0.020)]
    assert [g[0] for g in b["idle_gaps"]] == ["bench.request", "bench.wait",
                                              "bench.step"]
    assert [g[1] for g in b["idle_gaps"]] == pytest.approx(
        [0.025, 0.020, 0.005])
    assert all(sp.name != "bench.plan" for sp in s.spans)


def test_summarize_needs_one_window():
    with pytest.raises(ValueError):
        trace.summarize({"/device:TPU:0": ([], [])}, [])


# Two small traces recorded on one TPU v5e chip with ``--trace 1``: eight
# batch-32 forwards of resnet101_t3, and 30 requests of whisper_tiny_fe.
RECORDED = {
    "resnet101_t3.infer_b32": {"window_s": 0.262391665,
                               "busy_s": 0.159741315, "launches": 7,
                               "mosaic_s": 0.143556, "other_s": 0.016185},
    "whisper_tiny_fe.serve_poisson": {"window_s": 0.341170535,
                                      "busy_s": 0.097142867,
                                      "launches": 696, "mosaic_s": 0.08823,
                                      "other_s": 0.008913},
}


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_recorded_chip_trace(cell):
    want = RECORDED[cell]
    s = trace.load_file(str(DATA / f"{cell}.xplane.pb.gz"))
    assert [d.name for d in s.devices] == ["/device:TPU:0"]
    d = s.fullest()
    assert s.window_s == pytest.approx(want["window_s"])
    assert d.busy_s == pytest.approx(want["busy_s"])
    assert s.busy_s == d.busy_s < s.window_s
    assert d.launches == want["launches"]
    assert d.kind_s["mosaic"] == pytest.approx(want["mosaic_s"], abs=1e-6)
    assert d.kind_s["other"] == pytest.approx(want["other_s"], abs=1e-6)
    assert d.kind_s["conv"] == d.kind_s["collective"] == 0.0
    assert d.exposed_collective_s == 0.0
    assert {sp.name for sp in s.spans} <= {"bench.step", "bench.wait",
                                           "bench.request"}
    b = s.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    assert "mec_conv_fused_pallas" in b["device_ops"][0][0]
    assert sum(g for _, g in b["idle_gaps"]) <= s.window_s - d.busy_s + 1e-9
