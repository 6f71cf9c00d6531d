"""Plan-driven conv serving (repro.serving.conv_service, DESIGN.md §9):
bucketing is deterministic and total over the admitted range, padding
never shrinks, warm and cold paths are bit-identical, warmup degrades
(never crashes) on plan-cache trouble, and the conv frontend feeds the
continuous-batching scheduler without disturbing token streams or EOS.
"""
import json
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import conv2d
from repro.serving.conv_service import (ConvService, ShapeClass,
                                        fit_prefix, parse_shape_classes,
                                        patch_embed_service,
                                        whisper_frontend_service)

_KEY = jax.random.key(0)


def _kernel(k_h=3, k_w=3, i_c=4, k_c=8):
    return jax.random.normal(_KEY, (k_h, k_w, i_c, k_c)) \
        * (k_h * k_w * i_c) ** -0.5


def _service(classes=((1, 12, 12), (2, 16, 16)), **kw):
    kw.setdefault("stride", 2)
    kw.setdefault("padding", 1)
    kw.setdefault("plan_mode", "analytic")
    return ConvService(_kernel(), classes=classes, **kw)


# ---------------------------------------------------------------- bucketing

def test_bucket_smallest_containing_class_wins():
    svc = _service()
    assert svc.bucket((1, 9, 11)) == ShapeClass(1, 12, 12)
    assert svc.bucket((1, 12, 12)) == ShapeClass(1, 12, 12)   # exact fit
    assert svc.bucket((1, 13, 5)) == ShapeClass(2, 16, 16)    # h forces up
    assert svc.bucket((2, 3, 3)) == ShapeClass(2, 16, 16)     # n forces up
    # 4-tuples (with channel) bucket like 3-tuples
    assert svc.bucket((1, 9, 11, 4)) == ShapeClass(1, 12, 12)


def test_bucket_deterministic_and_total():
    svc = _service()
    for n in range(1, 3):
        for h in range(1, 17):
            for w in range(1, 17):
                cls = svc.bucket((n, h, w))
                assert cls.contains(n, h, w)
                assert svc.bucket((n, h, w)) == cls        # deterministic
                assert svc.bucket((cls.n, cls.h, cls.w)) == cls  # idempotent
                # smallest: no strictly earlier class contains it
                for other in svc.classes:
                    if other < cls:
                        assert not other.contains(n, h, w)


def test_bucket_rejects_out_of_range_loudly():
    svc = _service()
    with pytest.raises(ValueError, match="fits no shape class"):
        svc.bucket((1, 17, 4))
    with pytest.raises(ValueError, match="fits no shape class"):
        svc.bucket((3, 4, 4))
    with pytest.raises(ValueError, match="non-positive"):
        svc.bucket((1, 0, 4))
    with pytest.raises(ValueError, match="channels"):
        svc.bucket((1, 8, 8, 3))          # service convolves 4 channels
    with pytest.raises(ValueError, match="not"):
        svc.bucket((1, 8))


def test_parse_shape_classes():
    assert parse_shape_classes("1x32x32,4x64x64") == (
        ShapeClass(1, 32, 32), ShapeClass(4, 64, 64))
    with pytest.raises(ValueError, match="NxHxW"):
        parse_shape_classes("1x32")
    with pytest.raises(ValueError, match="no shape classes"):
        parse_shape_classes(",")


def test_duplicate_and_invalid_classes_rejected():
    with pytest.raises(ValueError, match="duplicate"):
        _service(classes=((1, 12, 12), (1, 12, 12)))
    with pytest.raises(ValueError, match="non-positive"):
        _service(classes=((1, 0, 12),))


def test_same_padding_rejected():
    # SAME's pad split depends on the input size, so a request and its
    # padded class would disagree on window alignment — the exact-slice
    # argument (module docstring) only holds for size-independent pads.
    with pytest.raises(ValueError, match="SAME"):
        _service(padding="SAME")


# ---------------------------------------------------------------- padding

def test_padding_never_shrinks_and_preserves_data():
    svc = _service()
    x = jax.random.normal(jax.random.key(1), (1, 9, 11, 4))
    cls = svc.bucket(x.shape)
    padded = svc.pad_to_class(x, cls)
    assert padded.shape == (cls.n, cls.h, cls.w, 4)
    assert all(p >= r for p, r in zip(padded.shape, x.shape))
    np.testing.assert_array_equal(np.asarray(padded[:1, :9, :11]),
                                  np.asarray(x))
    assert float(jnp.abs(padded[:, 9:]).sum()) == 0.0
    assert float(jnp.abs(padded[:, :, 11:]).sum()) == 0.0


# --------------------------------------------------------------- execution

def test_execute_matches_direct_conv_on_request():
    svc = _service()
    svc.warm()
    for shape in ((1, 9, 11, 4), (1, 12, 12, 4), (2, 13, 16, 4)):
        x = jax.random.normal(jax.random.key(2), shape)
        got = svc(x)
        ref = conv2d(x, svc.kernel, stride=2, padding=1,
                     algorithm="direct")
        assert got.shape == ref.shape == svc.request_out_shape(shape)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-5, atol=2e-6)


def test_warm_vs_cold_bit_identical():
    x = jax.random.normal(jax.random.key(3), (1, 10, 13, 4))
    warm = _service()
    warm.warm()
    assert len(warm.warmup.plans) == len(warm.classes)
    cold = _service()            # never warmed: lazy per-class resolve
    y_warm, y_cold = warm(x), cold(x)
    np.testing.assert_array_equal(np.asarray(y_warm), np.asarray(y_cold))
    assert np.asarray(y_warm).tobytes() == np.asarray(y_cold).tobytes()


def test_valid_padding_service():
    svc = ConvService(_kernel(4, 4, 3, 8), stride=4, padding="VALID",
                      classes=[(1, 16, 16), (1, 32, 32)],
                      plan_mode="analytic")
    x = jax.random.normal(jax.random.key(4), (1, 24, 20, 3))
    got = svc(x)
    ref = conv2d(x, svc.kernel, stride=4, padding="VALID",
                 algorithm="direct")
    assert got.shape == ref.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_fit_prefix_crops_and_pads():
    x = jnp.arange(24, dtype=jnp.float32).reshape(1, 6, 4)
    assert fit_prefix(x, 4).shape == (1, 4, 4)
    np.testing.assert_array_equal(np.asarray(fit_prefix(x, 4)),
                                  np.asarray(x[:, :4]))
    padded = fit_prefix(x, 9)
    assert padded.shape == (1, 9, 4)
    assert float(jnp.abs(padded[:, 6:]).sum()) == 0.0


# ------------------------------------------------------- warmup degradation

def test_warmup_survives_cache_dir_that_is_a_file(tmp_path, monkeypatch):
    from repro.plan.cache import reset_global_plan_cache
    bogus = tmp_path / "not-a-dir"
    bogus.write_text("i am a file, not a cache directory")
    monkeypatch.setenv("REPRO_PLAN_CACHE_DIR", str(bogus))
    reset_global_plan_cache()
    try:
        svc = _service(plan_mode="cached")
        report = svc.warm()                      # must not raise
        assert len(report.plans) == len(svc.classes)
        # the breakage is COUNTED, not hidden: reads under a non-directory
        # fail as OSError -> PlanCache.io_errors -> the report
        assert report.plan_cache_io_errors >= 1
        # and the service still serves correct results
        x = jax.random.normal(jax.random.key(5), (1, 9, 11, 4))
        ref = conv2d(x, svc.kernel, stride=2, padding=1,
                     algorithm="direct")
        np.testing.assert_allclose(np.asarray(svc(x)), np.asarray(ref),
                                   rtol=2e-5, atol=2e-6)
    finally:
        reset_global_plan_cache()


def test_warmup_survives_corrupt_cache_file(tmp_path, monkeypatch):
    from repro.plan.cache import reset_global_plan_cache, global_plan_cache
    monkeypatch.setenv("REPRO_PLAN_CACHE_DIR", str(tmp_path))
    reset_global_plan_cache()
    try:
        corrupt = global_plan_cache().path()
        corrupt.parent.mkdir(parents=True, exist_ok=True)
        corrupt.write_text("{ this is not json")
        svc = _service(plan_mode="cached")
        report = svc.warm()
        assert len(report.plans) == len(svc.classes)
        assert report.plan_cache_io_errors >= 1
    finally:
        reset_global_plan_cache()


def test_warmup_report_renders_plan_table():
    svc = _service()
    report = svc.warm()
    text = report.render()
    assert "warmed 2/2 shape class(es)" in text
    for cls in svc.classes:
        assert f"-- class {cls.tag()} --" in text
    assert "ConvPlan[" in text                  # ConvPlan.explain() output
    assert "0 plan-cache I/O error(s)" in report.summary()


def test_warmup_report_cli(capsys, tmp_path, monkeypatch):
    from repro.serving.__main__ import main
    # the CLI places JAX's compile cache; keep it out of the checkout
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    rc = main(["--warmup-report", "--kernel", "3x3x2x4", "--stride", "2",
               "--padding", "1", "--shape-classes", "1x8x8",
               "--plan-mode", "analytic"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "warmed 1/1 shape class(es)" in out
    assert "-- class 1x8x8 --" in out


# -------------------------------------------------------------- scheduler

def test_scheduler_drains_mixed_shape_image_stream():
    """Variable-shape images -> warmed patch-embed service -> vision
    tokens -> continuous batcher.  Token streams must be exactly the
    solo prefill/decode reference and EOS must still free slots."""
    from repro.configs.archs import smoke_config
    from repro.models import serve
    from repro.models.lm import LM
    from repro.serving.scheduler import ContinuousBatcher, Request

    cfg = smoke_config("llava-next-34b")
    assert cfg.family == "vlm"
    model = LM(cfg)
    params = model.init(jax.random.key(0))
    frontend, svc = patch_embed_service(
        jax.random.key(1), 3, cfg.d_model, 4,
        classes=[(1, 8, 8), (1, 16, 16)], prefix_len=cfg.prefix_len,
        plan_mode="analytic")
    assert len(svc.warmup.plans) == 2

    image_shapes = [(1, 6, 7, 3), (1, 8, 8, 3), (1, 13, 16, 3)]
    prompts = [jax.random.randint(jax.random.key(10 + i), (4 + i,), 0,
                                  cfg.vocab, jnp.int32) for i in range(3)]
    visions = [frontend(jax.random.normal(jax.random.key(20 + i), s))
               for i, s in enumerate(image_shapes)]
    for v in visions:
        assert v.shape == (1, cfg.prefix_len, cfg.d_model)

    def solo(prompt, vision, n, max_len=64):
        logits, cache = serve.prefill(
            model, params, {"tokens": prompt[None], "vision": vision},
            max_len=max_len)
        out = [int(jnp.argmax(logits[0]))]
        tok = jnp.asarray([[out[-1]]], jnp.int32)
        for _ in range(n - 1):
            logits, cache = serve.decode_step(model, params, cache, tok)
            out.append(int(jnp.argmax(logits[0])))
            tok = jnp.asarray([[out[-1]]], jnp.int32)
        return out

    refs = [solo(p, v, 5) for p, v in zip(prompts, visions)]

    # 3 mixed-shape requests through 2 slots: forces queueing + recycling
    batcher = ContinuousBatcher(model, params, n_slots=2, max_len=64)
    for i, (p, v) in enumerate(zip(prompts, visions)):
        batcher.submit(Request(rid=i, prompt=p, max_new_tokens=5,
                               extras={"vision": v}))
    done = batcher.run_until_done()
    assert len(done) == 3
    for req in done:
        assert req.out == refs[req.rid], (req.rid, req.out, refs[req.rid])

    # EOS through the frontend path still stops the stream and frees the
    # slot (the scheduler must not lose completion rules for extras)
    eos = refs[0][1]
    batcher = ContinuousBatcher(model, params, n_slots=1, max_len=64)
    batcher.submit(Request(rid=0, prompt=prompts[0], max_new_tokens=5,
                           eos_id=eos, extras={"vision": visions[0]}))
    done = batcher.run_until_done()
    assert done[0].out == refs[0][:refs[0].index(eos) + 1]
    assert int(batcher.cache["lens"][0]) == -1


# ------------------------------------------------------------ serve report

def test_committed_serve_baseline_is_valid():
    from repro.bench.report import validate_report
    path = pathlib.Path(__file__).resolve().parents[1] \
        / "benchmarks" / "baselines" / "serve.json"
    doc = json.loads(path.read_text())
    assert validate_report(doc) == []
    assert doc["suite"] == "serve"
    recs = doc["results"]
    assert {r["serve_mode"] for r in recs} == {"warm", "cold", "auto"}
    # the committed baseline must witness the tentpole claim: warm p50
    # no worse than per-call auto dispatch on every class cell
    by = {(r["scenario"], r["serve_mode"]): r for r in recs}
    for cell in {r["scenario"] for r in recs}:
        assert by[(cell, "warm")]["p50_us"] <= by[(cell, "auto")]["p50_us"]
        assert by[(cell, "warm")]["warmup_warnings"] == 0


def test_serve_record_schema_gates():
    from repro.bench.report import validate_report
    rec = {
        "scenario": "x_c1x8x8", "algorithm": "warm", "dtype": "float32",
        "weight": 1,
        "spec": {k: 1 for k in ("i_n", "i_h", "i_w", "i_c", "k_h", "k_w",
                                "k_c", "s_h", "s_w")},
        "run_spec": {k: 1 for k in ("i_n", "i_h", "i_w", "i_c", "k_h",
                                    "k_w", "k_c", "s_h", "s_w")},
        "overhead_elems": 0, "overhead_bytes": 0, "flops": 1.0,
        "run_flops": 1.0, "auto_algorithm": "direct", "out_shape": [1],
        "us_per_call": None, "timing": None, "hlo_flops": None,
        "hlo_bytes": None, "serve_mode": "warm",
        # deliberately missing shape_class etc.
    }
    doc = {"schema_version": 1, "suite": "serve",
           "environment": {k: "x" for k in ("jax", "numpy", "python",
                                            "backend", "device_count",
                                            "platform")},
           "harness": {}, "results": [rec]}
    errs = validate_report(doc)
    assert any("serve cell missing" in e for e in errs)
    rec.update(shape_class="1x8x8", n_classes=1, n_requests=4,
               warmup_warnings=0, plan_cache_io_errors=0)
    assert validate_report(doc) == []


def test_whisper_frontend_service_shapes():
    frontend, services = whisper_frontend_service(
        jax.random.key(6), n_mels=8, d_model=16,
        classes=[(1, 12, 1), (1, 24, 1)], plan_mode="analytic")
    for t in (9, 12, 24):
        out = frontend(jax.random.normal(jax.random.key(7), (1, t, 8)))
        # class execution slices the CLIP's true output back out: the
        # stride-2 (1,1)-padded layer yields ceil(t/2) frames, not a
        # class-sized result
        assert out.shape == (1, (t + 1) // 2, 16)
        assert services[0].bucket((1, t, 1)) in services[0].warmup.plans
