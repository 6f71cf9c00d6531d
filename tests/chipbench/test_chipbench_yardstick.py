"""The benchmark's yardstick: peaks by device kind, shape-based counts, and
roofline shares that cannot pass 100%."""
import pytest

from chipbench import bench, trace, yardstick


def _config(name):
    import json
    return json.loads((bench.HERE / "configs" / f"{name}.json").read_text())


def test_resnet101_t3_counts():
    cfg = _config("resnet101_t3")
    convs = yardstick.chain_convs(cfg["stages"], 1)
    assert len(convs) == 34
    params = sum(c.k_h * c.k_w * c.i_c * c.o_c for c in convs)
    assert params == cfg["conv_params"] == 21_544_960
    assert yardstick.forward_flops(convs) / 1e9 == pytest.approx(12.399,
                                                                 abs=5e-4)
    assert yardstick.train_flops(cfg["stages"], 1) / 1e9 == pytest.approx(
        31.50, abs=5e-3)
    # Per layer: cv4 4.769 GFLOP, every 3x3 conv 0.2312 GFLOP.
    assert convs[0].flops / 1e9 == pytest.approx(4.769, abs=5e-4)
    assert {round(c.flops / 1e9, 4) for c in convs[1:]} == {0.2312}


def test_unknown_device_is_an_error():
    with pytest.raises(yardstick.UnknownDevice):
        yardstick.peaks("TPU v99")
    with pytest.raises(yardstick.UnknownDevice):
        yardstick.peak_flops("TPU v5 lite", "float32")
    assert yardstick.peak_flops("TPU v5 lite", "bfloat16") == 197e12
    assert yardstick.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def _synthetic(op_seconds, forwards, idle_s=0.0):
    """A trace of ``forwards`` forwards whose ops take ``op_seconds`` each,
    back to back, then ``idle_s`` idle."""
    ops, t = [], 0.0
    for _ in range(forwards):
        for i, s in enumerate(op_seconds):
            ops.append(trace.Op(f"fusion.{i}", t, t + s * 1e9, "mosaic"))
            t += s * 1e9
    spans = [trace.Span(trace.WINDOW_SPAN, 0.0, t + idle_s * 1e9)]
    return trace.summarize({"/device:TPU:0": (ops, [])}, spans)


@pytest.mark.parametrize("slowdown,idle", [(1.0, 0.0), (1.0, 0.5),
                                           (3.0, 0.0), (3.0, 2.0)])
def test_roofline_shares_stay_within_100(slowdown, idle):
    """A device that runs every conv at exactly its roofline reads 100%,
    a slower one less; MFU is below the roofline share."""
    cfg = _config("resnet101_t3")
    batch, forwards = 32, 5
    convs = yardstick.chain_convs(cfg["stages"], batch)
    kind, dtype = "TPU v5 lite", "bfloat16"
    fl = yardstick.peak_flops(kind, dtype)
    bw = yardstick.peaks(kind)["hbm_bytes_per_s"]
    op_s = [c.roofline_s(fl, bw, 2) * slowdown for c in convs]
    summary = _synthetic(op_s, forwards, idle)
    run = bench.Run(attempted=forwards, failed=0,
                    window_s=summary.window_s, e2e={},
                    work={"forwards": forwards, "traced_forwards": forwards,
                          "images": forwards * batch,
                          "batch": batch, "convs": convs, "dtype": dtype,
                          "flops_per_image":
                              yardstick.forward_flops(convs) / batch},
                    checks=[], memory_peak_bytes=0)
    device = {"kind": kind, "count": 1}
    readers = {m: bench.load_module(bench.HERE / "metrics" / f"{m}.py")
               for m in ("infer.conv_roofline", "infer.mfu_pct")}
    roof = readers["infer.conv_roofline"].read(
        run=run, trace=summary, device=device, config=cfg, traffic={})
    mfu = readers["infer.mfu_pct"].read(
        run=run, trace=summary, device=device, config=cfg, traffic={})
    assert roof == pytest.approx(100.0 / slowdown)
    assert roof <= 100.0 + 1e-9
    assert 0 < mfu <= roof + 1e-9
