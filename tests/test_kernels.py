"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp
oracles in repro.kernels.ref (interpret mode on CPU, per assignment)."""
import hypothesis
import hypothesis.strategies as st
import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.ops import mec_conv1d_tpu, mec_conv2d_tpu

SWEEP = [
    # (ih, iw, ic, kh, kw, kc, stride)
    (7, 7, 1, 3, 3, 1, 1),
    (12, 14, 3, 5, 3, 8, 2),
    (9, 9, 4, 3, 3, 6, 1),
    (11, 13, 2, 4, 5, 3, (2, 3)),
    (16, 16, 8, 7, 7, 16, 2),
    (8, 8, 3, 1, 1, 4, 1),
    (24, 24, 6, 5, 5, 16, 1),
    (227 // 4, 227 // 4, 3, 11, 11, 8, 4),   # cv1-like geometry, reduced
]
DTYPES = [jnp.float32, jnp.bfloat16]


def _rand(shape, seed, dtype):
    x = np.random.RandomState(seed).randn(*shape).astype(np.float32)
    return jnp.asarray(x, dtype)


@pytest.mark.parametrize("geom", SWEEP)
@pytest.mark.parametrize("dtype", DTYPES)
def test_mec_conv2d_kernel(geom, dtype):
    ih, iw, ic, kh, kw, kc, s = geom
    inp = _rand((2, ih, iw, ic), 0, dtype)
    ker = _rand((kh, kw, ic, kc), 1, dtype)
    oracle = ref.conv2d_ref(inp.astype(jnp.float32),
                            ker.astype(jnp.float32), s)
    out = mec_conv2d_tpu(inp, ker, s, interpret=True)
    assert out.shape == oracle.shape
    tol = 2e-4 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(oracle), rtol=tol, atol=tol)


@pytest.mark.parametrize("t,c,kw", [(10, 5, 4), (1024, 256, 4), (33, 7, 3),
                                    (512, 64, 2), (5, 3, 4)])
@pytest.mark.parametrize("dtype", DTYPES)
def test_mec_conv1d_kernel(t, c, kw, dtype):
    x = _rand((2, t, c), 3, dtype)
    k = _rand((kw, c), 4, dtype)
    oracle = ref.conv1d_ref(x.astype(jnp.float32), k.astype(jnp.float32))
    out = mec_conv1d_tpu(x, k, interpret=True)
    tol = 2e-4 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(oracle), rtol=tol, atol=tol)


@hypothesis.given(
    st.integers(4, 20), st.integers(4, 20), st.integers(1, 6),
    st.integers(1, 4), st.integers(1, 4), st.integers(1, 8),
    st.integers(1, 3), st.integers(1, 3))
@hypothesis.settings(max_examples=25, deadline=None)
def test_mec_fused_kernel_property(ih, iw, ic, kh, kw, kc, sh, sw):
    hypothesis.assume(ih >= kh and iw >= kw)
    inp = _rand((1, ih, iw, ic), 5, jnp.float32)
    ker = _rand((kh, kw, ic, kc), 6, jnp.float32)
    oracle = ref.conv2d_ref(inp, ker, (sh, sw))
    out = mec_conv2d_tpu(inp, ker, (sh, sw), interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                               rtol=2e-4, atol=2e-4)


# (name, input (n, i_h, i_w, i_c), kernel (k_h, k_w, k_c), stride, w_blk,
#  what the blocking must show) — w_blk None is the planner's pick_w_blk.
FUSED_BLOCKING = [
    # the resnet101_t3 stages (pre-padded 3x3 inputs) at batch 2
    ("cv4", (2, 224, 224, 64), (7, 7, 64), 2, None, "rows"),
    ("cv9", (2, 58, 58, 64), (3, 3, 64), 1, None, "plane"),
    ("cv10", (2, 30, 30, 128), (3, 3, 128), 1, None, "images"),
    ("cv11", (2, 16, 16, 256), (3, 3, 256), 1, None, "images"),
    ("cv12", (2, 9, 9, 512), (3, 3, 512), 1, None, "images"),
    # o_h = 21 in row blocks of 11: the last block runs past the rows
    ("ragged_rows", (1, 23, 66, 2), (3, 3, 512), 1, None, "ragged_rows"),
    # 11 images in blocks of 6: the last block runs past the batch
    ("ragged_images", (11, 10, 18, 2), (3, 3, 512), 1, None,
     "ragged_images"),
    ("7x7_s2", (2, 29, 31, 8), (7, 7, 16), 2, None, "plane"),
    # o_w = 38 in 16-column blocks
    ("w_blk_lt_o_w", (2, 9, 40, 4), (3, 3, 8), 1, 16, "columns"),
    # whisper's conv1d as (time, 1): the unit width is squeezed
    ("conv1d", (2, 40, 1, 8), (3, 1, 16), 1, None, "squeeze"),
    ("conv1d_s2", (2, 41, 1, 8), (3, 1, 16), (2, 1), None, "squeeze"),
]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("case", FUSED_BLOCKING, ids=[c[0] for c in
                                                      FUSED_BLOCKING])
def test_mec_fused_blocking_matches_direct(case, dtype):
    """The fused kernel's row, image and column blocks (fused_blocks)
    give direct's answer at the resnet101_t3 stage shapes and at every
    edge of the blocking, in the output dtype."""
    from repro.kernels.mec_conv import fused_blocks
    from repro.kernels.ops import pick_w_blk
    _, (n, ih, iw, ic), (kh, kw, kc), s, w_blk, shows = case
    s_h, s_w = (s, s) if isinstance(s, int) else s
    o_h, o_w = (ih - kh) // s_h + 1, (iw - kw) // s_w + 1
    w_blk = w_blk or pick_w_blk(o_w, kc)
    fb = fused_blocks(n, ih, iw, ic, kh, kw, kc, s_h, s_w, w_blk,
                      jnp.dtype(dtype).itemsize)
    n_c, n_b, n_h, n_w = fb.grid
    assert {
        "rows": fb.nb == 1 and 8 <= fb.hb < o_h,
        "plane": fb.hb == o_h and n_h == 1,
        "images": fb.nb == n and fb.hb == o_h,
        "ragged_rows": n_h > 1 and o_h % fb.hb != 0,
        "ragged_images": n_b > 1 and n % fb.nb != 0,
        "columns": n_w > 1 and fb.w_blk == w_blk,
        "squeeze": fb.squeeze and fb.o_w == o_h and fb.dot_rows >= o_h,
    }[shows], fb.describe()
    inp = _rand((n, ih, iw, ic), 11, dtype)
    ker = _rand((kh, kw, ic, kc), 12, dtype) / np.sqrt(kh * kw * ic)
    oracle = ref.conv2d_ref(inp.astype(jnp.float32),
                            ker.astype(jnp.float32), (s_h, s_w))
    out = mec_conv2d_tpu(inp, ker, (s_h, s_w), interpret=True,
                         w_blk=w_blk)
    assert out.shape == oracle.shape and out.dtype == dtype
    tol = 2e-4 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(oracle), rtol=tol, atol=tol)


def _pallas_call(jaxpr, name):
    """The ``pallas_call`` eqn named ``name`` in ``jaxpr``, nested or not."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call" and \
                eqn.params["name"] == name:
            return eqn
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", None)
            inner = getattr(inner, "jaxpr", inner)
            if inner is not None and hasattr(inner, "eqns"):
                found = _pallas_call(inner, name)
                if found is not None:
                    return found
    return None


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("case", FUSED_BLOCKING, ids=[c[0] for c in
                                                      FUSED_BLOCKING])
def test_weight_grad_reads_only_padded_data(case, dtype):
    """The weight gradient's kernel runs the forward's grid
    (``fused_blocks``), and every input window and cotangent block it
    reads, at every grid step, lies inside the arrays the wrapper padded
    to the windows' extent: no read runs past an array into memory that
    may hold NaN, so pixels past the conv's output add exactly zero."""
    import jax
    from jax.experimental import pallas as pl
    from repro.kernels.mec_conv import fused_blocks, mec_weight_grad_pallas
    from repro.kernels.ops import pick_w_blk
    _, (n, ih, iw, ic), (kh, kw, kc), s, w_blk, _ = case
    s_h, s_w = (s, s) if isinstance(s, int) else s
    o_h, o_w = (ih - kh) // s_h + 1, (iw - kw) // s_w + 1
    w_blk = w_blk or pick_w_blk(o_w, kc)
    fb = fused_blocks(n, ih, iw, ic, kh, kw, kc, s_h, s_w, w_blk,
                      jnp.dtype(dtype).itemsize)
    eqn = _pallas_call(jax.make_jaxpr(
        lambda x, g: mec_weight_grad_pallas(x, g, kh, kw, (s_h, s_w),
                                            w_blk=w_blk))(
        jax.ShapeDtypeStruct((n, ih, iw, ic), dtype),
        jax.ShapeDtypeStruct((n, o_h, o_w, kc), dtype)).jaxpr,
        "mec_fused_wgrad")
    gm = eqn.params["grid_mapping"]
    assert tuple(gm.grid) == fb.grid
    x_map, g_map = gm.block_mappings[:gm.num_inputs]
    assert x_map.array_aval.shape[:2] + x_map.array_aval.shape[3:4] == \
        fb.window_extent
    assert g_map.array_aval.shape[:3] == fb.out_extent
    for bm in (x_map, g_map):
        shape = bm.array_aval.shape
        for d in bm.block_shape:
            assert getattr(d, "padding", (0, 0)) == (0, 0), bm.block_shape
        for step in np.ndindex(*fb.grid):
            starts = jax.core.eval_jaxpr(bm.index_map_jaxpr.jaxpr,
                                         bm.index_map_jaxpr.consts, *step)
            for d, start, size in zip(bm.block_shape, starts, shape):
                lo = int(start) * (1 if isinstance(d, pl.Element)
                                   else d.block_size)
                assert 0 <= lo and lo + d.block_size <= size, \
                    (step, bm.block_shape, shape)


def test_accumulator_budget_env_and_default(monkeypatch):
    """pick_w_blk's VMEM accumulator budget is env-configurable
    (REPRO_MEC_ACC_BYTES) instead of a hard-coded ~2 MiB."""
    from repro.kernels import ops
    monkeypatch.delenv(ops.ACC_BYTES_ENV, raising=False)
    # off-TPU default: the v5e 16 MiB/8 heuristic
    assert ops.accumulator_budget() == 2 << 20
    assert ops.pick_w_blk(4096, 8) == 512          # hits the 512 cap
    monkeypatch.setenv(ops.ACC_BYTES_ENV, "4096")
    with pytest.warns(DeprecationWarning):
        assert ops.accumulator_budget() == 4096
    with pytest.warns(DeprecationWarning):
        assert ops.pick_w_blk(4096, 8) == 128      # 4096 / (4*8) = 128
    monkeypatch.setenv(ops.ACC_BYTES_ENV, "0x1000")  # hex accepted
    with pytest.warns(DeprecationWarning):
        assert ops.accumulator_budget() == 4096
    monkeypatch.setenv(ops.ACC_BYTES_ENV, "-1")
    with pytest.raises(ValueError), pytest.warns(DeprecationWarning):
        ops.accumulator_budget()
    # explicit argument still wins over everything
    assert ops.pick_w_blk(4096, 8, target_bytes=2 << 20) == 512


def test_acc_bytes_env_deprecation_boundary(monkeypatch, recwarn):
    """Satellite: a direct REPRO_MEC_ACC_BYTES read outside the plan
    path warns DeprecationWarning (pointing at ConvPlan.w_blk /
    plan_conv2d) with unchanged behaviour; the planner's read — the
    supported migration target — stays silent."""
    from repro.kernels import ops
    monkeypatch.setenv(ops.ACC_BYTES_ENV, "4096")
    with pytest.warns(DeprecationWarning, match="ConvPlan"):
        assert ops.accumulator_budget() == 4096    # value unchanged
    with pytest.warns(DeprecationWarning, match="plan_conv2d"):
        assert ops.pick_w_blk(4096, 8) == 128
    # the plan path: same resolved value, no warning
    assert ops.pick_w_blk(4096, 8, _warn_env=False) == 128
    from repro.core.convspec import ConvSpec
    from repro.plan import plan_conv2d
    spec = ConvSpec(1, 16, 16, 4, 3, 3, 8, 1, 1)
    n_before = len(recwarn)
    plan = plan_conv2d(spec, backend="tpu")        # Pallas pick -> w_blk
    deprecations = [w for w in recwarn.list[n_before:]
                    if issubclass(w.category, DeprecationWarning)]
    assert deprecations == []
    assert plan.w_blk == ops.pick_w_blk(spec.o_w, spec.k_c, _warn_env=False)
    # no env: nothing warns anywhere
    monkeypatch.delenv(ops.ACC_BYTES_ENV)
    n_before = len(recwarn)
    ops.accumulator_budget()
    assert not [w for w in recwarn.list[n_before:]
                if issubclass(w.category, DeprecationWarning)]


def test_pick_w_blk_never_exceeds_explicit_budget():
    """Regression: the 8-column sublane floor used to override a small
    explicit target_bytes (pick_w_blk(1000, 4, target_bytes=64) -> an
    8-column block = 128 accumulator bytes, 2x the budget)."""
    from repro.kernels import ops
    blk = ops.pick_w_blk(1000, 4, target_bytes=64)
    assert blk * 4 * 4 <= 64, (blk, blk * 4 * 4)
    assert blk == 4
    # sweep: an explicit budget >= one f32 column is never exceeded
    for k_c in (1, 3, 8, 64):
        for budget in (4 * k_c, 64, 512, 4096, 1 << 20):
            if budget < 4 * k_c:
                continue          # below the 1-column minimum
            blk = ops.pick_w_blk(10_000, k_c, target_bytes=budget)
            assert 1 <= blk <= 512
            assert blk * 4 * k_c <= budget, (k_c, budget, blk)
    # sub-column budgets clamp to the 1-column minimum (smallest
    # accumulator that exists) rather than 0
    assert ops.pick_w_blk(16, 64, target_bytes=8) == 1
    # the implicit device budget keeps its 8-column sublane floor
    assert ops.pick_w_blk(1000, 1 << 20) == 8


class _FakeTPU:
    platform = "tpu"

    def __init__(self, kind):
        self.device_kind = kind


def test_tpu_backend_refuses_interpret_and_unlowerable_modes(monkeypatch):
    """On a TPU backend the kernels compile through Mosaic or fail loudly:
    interpret=True is an error, raised before any tracing."""
    import jax
    from repro.kernels import ops
    assert ops.resolve_interpret(None) is True          # CPU: interpreter
    assert ops.resolve_interpret(True) is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.resolve_interpret(None) is False
    assert ops.resolve_interpret(False) is False
    with pytest.raises(ValueError, match="interpret=True"):
        ops.resolve_interpret(True)
    inp, ker = _rand((1, 6, 6, 2), 0, jnp.float32), \
        _rand((3, 3, 2, 4), 1, jnp.float32)
    with pytest.raises(ValueError, match="interpret=True"):
        mec_conv2d_tpu(inp, ker, interpret=True)
    with pytest.raises(ValueError, match="interpret=True"):
        mec_conv1d_tpu(jnp.ones((1, 8, 4)), jnp.ones((3, 4)),
                       interpret=True)


def test_vmem_bytes_unknown_tpu_kind_raises(monkeypatch):
    import jax
    from repro.kernels import ops
    assert ops.vmem_bytes() == ops.INTERPRET_VMEM      # CPU run
    monkeypatch.setattr(jax, "devices", lambda: [_FakeTPU("TPU v5 lite")])
    assert ops.vmem_bytes() == 16 << 20
    monkeypatch.setattr(jax, "devices", lambda: [_FakeTPU("TPU v99")])
    with pytest.raises(ValueError, match="TPU v99"):
        ops.vmem_bytes()
