"""The beyond-paper perf features must preserve training semantics."""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import hypothesis
import hypothesis.strategies as st
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.mec import mec_conv1d_depthwise, mec_conv1d_shift

REPO = pathlib.Path(__file__).resolve().parents[1]


@hypothesis.given(st.integers(1, 40), st.integers(1, 12), st.integers(1, 5))
@hypothesis.settings(max_examples=25, deadline=None)
def test_conv1d_shift_equals_lowered(t, c, k_w):
    """The fused (shift-add) conv dataflow is numerically identical to the
    lowered (gather) dataflow."""
    x = jnp.asarray(np.random.RandomState(t).randn(2, t, c), jnp.float32)
    k = jnp.asarray(np.random.RandomState(k_w).randn(k_w, c), jnp.float32)
    a = mec_conv1d_depthwise(x, k)
    b = mec_conv1d_shift(x, k)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


def test_dots_remat_and_sp_preserve_loss():
    """remat_policy='dots' and seq_parallel are exact transforms: the
    training losses must match full remat / no-SP bit-for-bit-ish on a
    DPxTP mesh."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import json
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import Mesh, NamedSharding
        from repro.configs.archs import smoke_config
        from repro.models.lm import LM
        from repro.optim.adamw import AdamWConfig
        from repro.parallel import sharding
        from repro.parallel.axes import default_rules
        from repro.training.steps import init_opt_state, make_train_step
        from repro.data.pipeline import SyntheticLMData

        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 2),
                    ("data", "model"))
        rules = default_rules(mesh)
        opt_cfg = AdamWConfig(lr=1e-3, total_steps=6, warmup_steps=2)

        def run(**overrides):
            cfg = smoke_config("yi-6b").with_(remat=True, **overrides)
            model = LM(cfg)
            params = model.init(jax.random.key(0))
            specs = sharding.param_specs(params, mesh)
            params = jax.tree.map(
                lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                params, specs)
            opt = init_opt_state(params)
            fn = jax.jit(make_train_step(model, opt_cfg, rules))
            data = SyntheticLMData(cfg, 8, 32)
            with mesh:
                losses = []
                for _ in range(6):
                    params, opt, m = fn(params, opt, data.next_batch())
                    losses.append(float(m["loss"]))
            return losses

        base = run()
        dots = run(remat_policy="dots")
        sp = run(seq_parallel=True)
        print(json.dumps({"base": base, "dots": dots, "sp": sp}))
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", prog], env=env, cwd=REPO,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    np.testing.assert_allclose(res["base"], res["dots"], rtol=2e-4)
    np.testing.assert_allclose(res["base"], res["sp"], rtol=2e-4)


def test_int8_a2a_is_differentiable_and_accurate():
    from repro.models.moe import _q8_a2a, int8_all_to_all  # noqa: F401
    # numerics of the quantize-dequantize pair (a2a on 1 device = identity)
    import numpy as np
    from jax.sharding import Mesh
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1), ("model",))
    x = jax.random.normal(jax.random.key(0), (8, 4, 16))

    def f(x):
        return int8_all_to_all(x, "model", 0, 1)

    with mesh:
        y = shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                      check_vma=False)(x)
        g = jax.grad(lambda x: jnp.sum(shard_map(
            f, mesh=mesh, in_specs=P(), out_specs=P(),
            check_vma=False)(x) ** 2))(x)
    rel = float(jnp.max(jnp.abs(y - x))) / float(jnp.max(jnp.abs(x)))
    assert rel < 0.02, rel
    assert bool(jnp.isfinite(g).all()) and float(jnp.abs(g).sum()) > 0


def test_triangular_attention_matches_masked():
    import numpy as np
    from repro.models.layers import chunked_attention, chunked_attention_tri
    for (s, h, kv, d, qc, kc) in [(33, 8, 4, 16, 8, 8), (64, 4, 2, 8, 16, 8),
                                  (17, 2, 2, 4, 4, 8)]:
        q = jax.random.normal(jax.random.key(1), (2, s, h, d))
        k = jax.random.normal(jax.random.key(2), (2, s, kv, d))
        v = jax.random.normal(jax.random.key(3), (2, s, kv, d))
        a = chunked_attention(q, k, v, causal=True, q_chunk=qc, kv_chunk=kc)
        b = chunked_attention_tri(q, k, v, q_chunk=qc, kv_chunk=kc)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5)
    # gradient parity
    g1 = jax.grad(lambda q: jnp.sum(chunked_attention(
        q, k, v, causal=True, q_chunk=4, kv_chunk=8) ** 2))(q)
    g2 = jax.grad(lambda q: jnp.sum(chunked_attention_tri(
        q, k, v, q_chunk=4, kv_chunk=8) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), rtol=1e-3,
                               atol=1e-4)


def test_attn_skip_masked_preserves_forward():
    import numpy as np
    from repro.configs.archs import smoke_config
    from repro.models.lm import LM
    cfg = smoke_config("yi-6b")
    model_a = LM(cfg)
    model_b = LM(cfg.with_(attn_skip_masked=True))
    params = model_a.init(jax.random.key(0))
    batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 24), 0,
                                          cfg.vocab, jnp.int32)}
    ha, _ = model_a.forward(params, batch)
    hb, _ = model_b.forward(params, batch)
    np.testing.assert_allclose(np.asarray(ha), np.asarray(hb), rtol=2e-4,
                               atol=2e-4)
