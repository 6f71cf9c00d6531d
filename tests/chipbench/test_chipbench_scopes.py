"""Device time by the program's own names: the XSpace metadata decoder,
the scoped trace reduction and the four readers that use it, on a
synthetic trace and on traces recorded on a TPU v5e chip."""
import gzip
import pathlib
import types

import pytest

from chipbench import bench, scopes, trace, xplane_meta, yardstick

DATA = pathlib.Path(__file__).parent / "data"
PROGRAM = ("conv2d", "conv2d_pad", "mec_fold", "conv2d_out",
           "mec_input_grad", "mec_weight_grad", "adamw_update", "mec_fused")


# ------------------------------------------------- a synthetic XSpace

def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _int(number, value):
    return _varint(number << 3) + _varint(value)


def _msg(number, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(number << 3 | 2) + _varint(len(payload)) + payload


def _entry(number, key, value):
    """One entry of a map field."""
    return _msg(number, _int(1, key) + _msg(2, value))


def _plane(name, stat_names, events, lines):
    """``stat_names``: {id: name}; ``events``: {id: (name, display,
    stats)}, a stat ``(metadata id, value)`` with a str, an int
    (uint64) or ``("ref", id)``; ``lines``: [(name, [(metadata id,
    start ns, duration ns)])]."""
    out = _msg(2, name)
    for line_name, evs in lines:
        body = _msg(2, line_name) + _int(3, 0)
        for mid, start, dur in evs:
            body += _msg(4, _int(1, mid) + _int(2, start * 1000) +
                         _int(3, dur * 1000))
        out += _msg(3, body)
    for mid, (ev_name, display, stats) in events.items():
        body = _int(1, mid) + _msg(2, ev_name) + _msg(4, display)
        for sid, value in stats:
            if isinstance(value, str):
                stat = _int(1, sid) + _msg(5, value)
            elif isinstance(value, tuple):
                stat = _int(1, sid) + _int(7, value[1])
            else:
                stat = _int(1, sid) + _int(3, value)
            body += _msg(5, stat)
        out += _entry(4, mid, body)
    for sid, stat_name in stat_names.items():
        out += _entry(5, sid, _int(1, sid) + _msg(2, stat_name))
    return out


TF_OP, DEDUP, PROG, REF = 1, 2, 3, 4
KERNEL = ('%k.1 = f32[8]{0} custom-call(bf16[8]{0} %copy.2), '
          'custom_call_target="tpu_custom_call"')
FOLD = "%copy.2 = bf16[8]{0} copy(bf16[8]{0} %x)"
KERNEL_DUP = ('%k.3 = f32[8]{0} custom-call(bf16[8]{0} %copy.2), '
              'custom_call_target="tpu_custom_call"')
WGRAD = "%fusion.4 = f32[8]{0} fusion(f32[8]{0} %g), kind=kLoop"
RELAYOUT = "%copy.5 = bf16[8]{0} copy(bf16[8]{0} %xs_0_)"
RELU = "%fusion.6 = bf16[8]{0} fusion(f32[8]{0} %k.3), kind=kLoop"
MODULE = "jit_step(7)"

DEVICE_EVENTS = {
    10: (KERNEL, "k.1", [
        (TF_OP, "jit(step)/jvp(conv2d)/jit(mec_conv_fused_pallas)/"
                "mec_fused/pallas_call:"), (PROG, 7)]),
    11: (FOLD, "copy.2", [(TF_OP, ("ref", REF)), (PROG, 7)]),
    12: (KERNEL_DUP, "k.3", [(DEDUP, "k.1"), (PROG, 7)]),
    13: (WGRAD, "fusion.4", [
        (TF_OP, "jit(step)/transpose(jvp(conv2d))/mec_weight_grad/"
                "dot_general:"), (PROG, 7)]),
    14: (RELAYOUT, "copy.5", []),
    15: (RELU, "fusion.6", [(TF_OP, "jit(step)/jit(relu)/max:"), (PROG, 7)]),
    16: (MODULE, "jit_step", []),
}
DEVICE_STATS = {TF_OP: "tf_op", DEDUP: "deduplicated_name",
                PROG: "program_id",
                REF: "jit(step)/jvp(conv2d)/jit(mec_conv_fused_pallas)/"
                     "mec_fold/reshape:"}
# (metadata id, start ns, duration ns); the window is 1000-2000 ns.
OPS = [(14, 900, 200),      # relayout, half before the window
       (11, 1100, 100),     # fold
       (10, 1200, 300),     # kernel
       (12, 1500, 100),     # the deduplicated kernel
       (15, 1600, 50),      # caller's ReLU
       (13, 1700, 200)]     # weight gradient
HOST_EVENTS = {1: ("bench.window", "bench.window", []),
               2: ("bench.step", "bench.step", [])}


def synthetic_xspace() -> bytes:
    device = _plane("/device:TPU:0", DEVICE_STATS, DEVICE_EVENTS,
                    [("XLA Modules", [(16, 1050, 900)]),
                     ("XLA Ops", OPS)])
    host = _plane("/host:CPU", {}, HOST_EVENTS,
                  [("python", [(1, 1000, 1000), (2, 1000, 50)])])
    return _msg(1, device) + _msg(1, host)


@pytest.fixture
def synthetic(tmp_path):
    path = tmp_path / "synthetic.xplane.pb"
    path.write_bytes(synthetic_xspace())
    return str(path)


def test_decoder_on_synthetic_xspace():
    """tf_op read directly and through a ref, deduplicated entries
    resolved to the instruction they name, entries with neither absent,
    and the op type dropped."""
    ops = xplane_meta.tf_ops(synthetic_xspace())
    assert list(ops) == ["/device:TPU:0"]
    assert ops["/device:TPU:0"] == {
        KERNEL: "jit(step)/jvp(conv2d)/jit(mec_conv_fused_pallas)/"
                "mec_fused/pallas_call",
        FOLD: "jit(step)/jvp(conv2d)/jit(mec_conv_fused_pallas)/mec_fold/"
              "reshape",
        KERNEL_DUP: "jit(step)/jvp(conv2d)/jit(mec_conv_fused_pallas)/"
                    "mec_fused/pallas_call",
        WGRAD: "jit(step)/transpose(jvp(conv2d))/mec_weight_grad/"
               "dot_general",
        RELU: "jit(step)/jit(relu)/max",
    }


@pytest.mark.parametrize("path,want", [
    ("jit(f)/conv2d/jit(mec_conv_fused_pallas)/mec_fused/pallas_call",
     ("conv2d", "mec_fused")),
    ("jit(f)/transpose(jvp(conv2d))/mec_input_grad/jit(mec_conv2d)/while",
     ("conv2d", "mec_input_grad")),
    ("jit(f)/jvp(conv2d)/conv2d_pad/jit(_pad)/pad", ("conv2d", "conv2d_pad")),
    ("jit(f)/adamw_update/sqrt", ("adamw_update",)),
    ("jit(f)/jit(relu)/max", ()),
    ("xs[0]", ()),
    ("", ()),
])
def test_scopes_of(path, want):
    assert scopes.scopes_of(path, PROGRAM) == want


def test_scoped_reduction_on_synthetic_xspace(synthetic):
    s = scopes.load_file(synthetic, names=PROGRAM)
    base = trace.load_file(synthetic)
    d = s.fullest()
    assert s.window_s == pytest.approx(1e-6)
    assert d.busy_s == pytest.approx(base.fullest().busy_s)
    assert d.busy_s == pytest.approx(100e-9 + 100e-9 + 300e-9 + 100e-9 +
                                     50e-9 + 200e-9)
    assert d.scope_kind_s == pytest.approx({
        ((), "other"): 100e-9 + 50e-9,                  # relayout, ReLU
        (("conv2d", "mec_fold"), "other"): 100e-9,
        (("conv2d", "mec_fused"), "mosaic"): 400e-9,
        (("conv2d", "mec_weight_grad"), "other"): 200e-9,
    })
    # No ops overlap here, so the scopes' seconds are the busy seconds.
    assert sum(d.scope_kind_s.values()) == pytest.approx(d.busy_s)
    assert d.scoped_s("conv2d") == pytest.approx(700e-9)
    assert d.scoped_s("conv2d", ("mosaic",)) == pytest.approx(400e-9)
    assert d.scoped_s("mec_fold") == pytest.approx(100e-9)
    assert d.scoped_s("mec_input_grad") == 0.0
    assert d.outside_s() == pytest.approx(150e-9)
    b = s.breakdown()
    assert b["device_ops"] == base.breakdown()["device_ops"]
    assert b["idle_gaps"] == base.breakdown()["idle_gaps"]
    assert [p for p, _ in b["scopes"]] == [
        "conv2d/mec_fused", "conv2d/mec_weight_grad", "outside",
        "conv2d/mec_fold"]


def test_scoped_reduction_without_program_names(synthetic):
    """A program that declares no names leaves every op outside."""
    d = scopes.load_file(synthetic, names=()).fullest()
    assert set(d.scope_kind_s) == {((), "mosaic"), ((), "other")}
    assert d.scoped_s("conv2d") == 0.0


# ------------------------------------------------------ the readers

def reader(name):
    return bench.load_module(bench.HERE / "metrics" / f"{name}.py")


DEVICE = {"kind": "TPU v5 lite", "count": 1}
CONVS = [yardstick.Conv(32, 14, 14, 256, 3, 3, 256, p_h=1, p_w=1)]


def _summary(scope_kind_s, launches=4):
    d = scopes.ScopedDeviceSummary(
        "/device:TPU:0", sum(scope_kind_s.values()),
        dict.fromkeys(trace.KINDS, 0.0), 0.0, launches, {}, [],
        scope_kind_s)
    return scopes.ScopedTraceSummary(1.0, [d], [])


FORWARD = {((), "other"): 0.003, (("conv2d", "conv2d_pad"), "other"): 0.001,
           (("conv2d", "mec_fold"), "other"): 0.002,
           (("conv2d", "mec_fused"), "mosaic"): 0.040,
           (("conv2d",), "conv"): 0.004, ((), "conv"): 0.001}
STEP = {(("conv2d", "mec_fused"), "mosaic"): 0.040,
        (("conv2d", "mec_input_grad"), "other"): 0.010,
        (("conv2d", "mec_input_grad"), "dot"): 0.014,
        (("conv2d", "mec_weight_grad"), "conv"): 0.020,
        (("adamw_update",), "other"): 0.002}


def _run(**work):
    return types.SimpleNamespace(work=dict(work))


def test_infer_readers_on_a_synthetic_summary():
    run = _run(traced_forwards=2, convs=CONVS, dtype="bfloat16")
    args = dict(device=DEVICE, config={}, traffic={})
    least = yardstick.roofline_s(CONVS, DEVICE["kind"], "bfloat16", 2)
    got = reader("infer.kernel_roofline").read(run=run, trace=_summary(
        FORWARD), **args)
    assert got == pytest.approx(100 * least * 2 / (0.040 + 0.004 + 0.001))
    got = reader("infer.executor_ms").read(run=run, trace=_summary(FORWARD),
                                           **args)
    assert got == pytest.approx(1e3 * 0.003 / 2)


def test_train_readers_on_a_synthetic_summary():
    args = dict(run=_run(steps=9), device=DEVICE, config={}, traffic={})
    s = _summary(STEP, launches=4)
    assert reader("train.bwd_input_ms").read(trace=s, **args) == \
        pytest.approx(1e3 * 0.024 / 4)
    assert reader("train.bwd_weight_ms").read(trace=s, **args) == \
        pytest.approx(1e3 * 0.020 / 4)


NEW_METRICS = ("infer.kernel_roofline", "infer.executor_ms",
               "train.bwd_input_ms", "train.bwd_weight_ms")


@pytest.mark.parametrize("name", NEW_METRICS)
def test_readers_read_nothing_without_program_scopes(name):
    """A trace without scopes (the plain reduction), or of a program
    that names nothing, gives no reading and raises nothing."""
    run = _run(traced_forwards=2, convs=CONVS, dtype="bfloat16", steps=9)
    args = dict(run=run, device=DEVICE, config={}, traffic={})
    plain = trace.TraceSummary(1.0, [trace.DeviceSummary(
        "/device:TPU:0", 0.05, dict.fromkeys(trace.KINDS, 0.01), 0.0, 4,
        {}, [])], [])
    unnamed = _summary({((), "mosaic"): 0.04, ((), "other"): 0.01})
    for t in (None, plain, unnamed):
        assert reader(name).read(trace=t, **args) is None


# ------------------------------------------- traces recorded on the chip

# The traces ``test_chipbench_trace`` reads, recorded before the program
# named its work: the scoped reduction leaves every number of the plain
# one as it was.
OLD = ("resnet101_t3.infer_b32", "whisper_tiny_fe.serve_poisson")


@pytest.mark.parametrize("cell", OLD)
def test_scoped_reduction_keeps_the_plain_one(cell):
    from test_chipbench_trace import RECORDED
    path = str(DATA / f"{cell}.xplane.pb.gz")
    s, base = scopes.load_file(path), trace.load_file(path)
    want = RECORDED[cell]
    d, b = s.fullest(), base.fullest()
    assert (s.window_s, s.busy_s, s.spans) == (base.window_s, base.busy_s,
                                               base.spans)
    for field in ("name", "busy_s", "kind_s", "exposed_collective_s",
                  "launches", "op_s", "gaps"):
        assert getattr(d, field) == getattr(b, field), field
    assert s.window_s == pytest.approx(want["window_s"])
    assert d.busy_s == pytest.approx(want["busy_s"])
    assert d.launches == want["launches"]
    assert d.kind_s["mosaic"] == pytest.approx(want["mosaic_s"], abs=1e-6)
    bd, bb = s.breakdown(), base.breakdown()
    assert bd["device_ops"] == bb["device_ops"]
    assert bd["idle_gaps"] == bb["idle_gaps"]
    assert sum(v for _, v in bd["scopes"]) <= \
        sum(d.scope_kind_s.values()) + 1e-12
    assert sum(d.scope_kind_s.values()) == pytest.approx(
        sum(d.kind_s.values()))


def _fixtures():
    return sorted(str(p) for p in DATA.glob("*.xplane.pb.gz"))


@pytest.mark.parametrize("path", _fixtures(),
                         ids=lambda p: pathlib.Path(p).name)
def test_decoder_matches_tensorflow(path):
    """Where TensorFlow is installed, its own XSpace parser gives each
    device event metadata entry the same name stack."""
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    raw = gzip.open(path).read()
    space = xplane_pb2.XSpace()
    space.ParseFromString(raw)
    mine = xplane_meta.tf_ops(raw)
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        names = {k: m.name for k, m in plane.stat_metadata.items()}

        def stats(meta):
            out = {}
            for st in meta.stats:
                kind = st.WhichOneof("value")
                value = names.get(st.ref_value) if kind == "ref_value" \
                    else getattr(st, kind)
                out[names[st.metadata_id]] = value
            return out

        entries = [(m.name, m.display_name, stats(m))
                   for m in plane.event_metadata.values()]
        direct = {(st.get("program_id"), disp): st["tf_op"]
                  for _, disp, st in entries if "tf_op" in st}
        want = {}
        for name, _, st in entries:
            op = st.get("tf_op") or direct.get(
                (st.get("program_id"), st.get("deduplicated_name")))
            if op:
                want.setdefault(name, op.rsplit(":", 1)[0])
        assert mine[plane.name] == want


# Two traces recorded on one TPU v5e chip after the program named its
# work (``--trace 1``): the 15 batch-32 forwards of a 0.3-s window, and a
# 0.4-s training window cut to its first 6 steps (device ops, program
# launches and the benchmark's spans kept).  The readings are those
# PERF.md records for these traces.
SCOPED = {
    "resnet101_t3.infer_b32": ({"traced_forwards": 15},
                               {"infer.kernel_roofline": 11.217,
                                "infer.executor_ms": 0.7826,
                                "infer.conv_roofline": 10.044,
                                "infer.wrapper_ms": 2.0960}),
    "resnet101_t3.train_b32": ({"steps": 6},
                               {"train.bwd_input_ms": 6.739,
                                "train.bwd_weight_ms": 13.535}),
}


def _scoped(cell):
    path = str(DATA / f"{cell}.scoped.xplane.pb.gz")
    cfg = bench.load_cell(cell).config
    work, want = SCOPED[cell]
    work = dict(work, convs=yardstick.chain_convs(cfg["stages"], 32),
                dtype=cfg["dtype"])
    return scopes.load_file(path), _run(**work), cfg, want


@pytest.mark.parametrize("cell", sorted(SCOPED))
def test_recorded_scoped_trace(cell):
    s, run, cfg, want = _scoped(cell)
    d = s.fullest()
    assert d.kind_s["mosaic"] > 0
    mosaic = {sc for sc, kind in d.scope_kind_s if kind == "mosaic"}
    assert mosaic == {("conv2d", "mec_fused")}
    got = {name: reader(name).read(run=run, trace=s, device=DEVICE,
                                   config=cfg, traffic={})
           for name in want}
    assert got == pytest.approx(want, rel=0.05)


def test_recorded_forward_scopes():
    s, run, cfg, _ = _scoped("resnet101_t3.infer_b32")
    d = s.fullest()
    args = dict(run=run, trace=s, device=DEVICE, config=cfg, traffic={})
    assert d.launches == run.work["traced_forwards"]
    # No two ops overlap: the program's scopes hold >= 90% of busy time.
    assert sum(d.scope_kind_s.values()) == pytest.approx(d.busy_s)
    assert d.scoped_s("conv2d") >= 0.9 * d.busy_s
    assert d.scoped_s("mec_fold") > 0 and d.scoped_s("conv2d_pad") > 0
    conv = reader("infer.conv_roofline").read(**args)
    kernel = reader("infer.kernel_roofline").read(**args)
    assert conv <= kernel <= 100
    assert reader("infer.executor_ms").read(**args) <= \
        reader("infer.wrapper_ms").read(**args)


def test_recorded_training_scopes():
    s, run, cfg, _ = _scoped("resnet101_t3.train_b32")
    d = s.fullest()
    assert d.launches == run.work["steps"]
    step = d.busy_s / d.launches
    bwd_input = d.scoped_s("mec_input_grad") / d.launches
    bwd_weight = d.scoped_s("mec_weight_grad") / d.launches
    assert bwd_input > 0 and bwd_weight > 0
    assert bwd_input + bwd_weight < step
    assert 0 < d.scoped_s("adamw_update") < d.scoped_s("mec_input_grad")
