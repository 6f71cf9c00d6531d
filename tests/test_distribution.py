"""Sharding rules, ZeRO-1 specs, gradient compression, and multi-device
behaviour (multi-device cases run in a subprocess with forced host
devices, since the main test process is single-device)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.archs import smoke_config
from repro.models.lm import LM
from repro.parallel import compression, sharding
from repro.parallel.axes import default_rules


def _fake_mesh(shape=(2, 4), axes=("data", "model")):
    """An abstract mesh for spec computation only (no devices needed)."""
    from jax.sharding import AbstractMesh
    return AbstractMesh(tuple(shape), tuple(axes))


def test_param_rules_respect_divisibility():
    cfg = smoke_config("qwen3-4b")          # kv=2 heads, model axis = 4
    model = LM(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    mesh = _fake_mesh()
    specs = sharding.param_specs(shapes, mesh)
    blocks = specs["blocks"]
    # wq column-sharded (out dim divisible), wo row-sharded
    assert blocks["attn"]["wq"]["w"] == P(None, None, "model")
    assert blocks["attn"]["wo"]["w"] == P(None, "model", None)
    assert blocks["mlp"]["gate"]["w"] == P(None, None, "model")
    assert blocks["mlp"]["down"]["w"] == P(None, "model", None)
    # embedding vocab-sharded
    assert specs["emb"] == P("model", None)
    # norms replicated
    assert specs["final_norm"] == P(None)


def test_zero1_adds_dp_axis():
    cfg = smoke_config("qwen3-4b")
    model = LM(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0)))
    mesh = _fake_mesh()
    p_specs = sharding.param_specs(shapes, mesh)
    z = sharding.zero1_specs(p_specs, shapes, mesh, zero_axes=("data",))
    # wq (L=4, 64, H*hd): first unsharded divisible dim (L) gets 'data'
    assert z["blocks"]["attn"]["wq"]["w"] == P("data", None, "model")
    # a previously replicated norm (L, d) is now DP-sharded
    spec = z["blocks"]["norm1"]
    assert "data" in str(spec)


def test_quantize_roundtrip_error_bound():
    x = jax.random.normal(jax.random.key(0), (1000,)) * 3.0
    q, s = compression.quantize(x)
    err = np.abs(np.asarray(compression.dequantize(q, s) - x))
    assert err.max() <= float(s) / 2 + 1e-6   # half-ulp of the int8 grid


def test_compressed_training_multidevice_subprocess():
    """4 fake host devices: int8-EF compressed DP training must converge
    and stay close to uncompressed training."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np, jax, jax.numpy as jnp, json
        from jax.sharding import Mesh
        from repro.configs.archs import smoke_config
        from repro.models.lm import LM
        from repro.optim.adamw import AdamWConfig
        from repro.parallel.axes import ShardingRules
        from repro.training.steps import (init_opt_state, make_train_step,
                                          make_compressed_train_step)
        from repro.data.pipeline import SyntheticLMData

        cfg = smoke_config("yi-6b")
        model = LM(cfg)
        mesh = Mesh(np.asarray(jax.devices()).reshape(4), ("data",))
        rules = ShardingRules(mesh=mesh, rules={"batch": "data"},
                              dp_axes=("data",), ep_axis=None, tp_axis=None)
        opt_cfg = AdamWConfig(lr=1e-3, total_steps=12, warmup_steps=2)

        def run(compressed):
            params = model.init(jax.random.key(0))
            opt = init_opt_state(params, compressed=compressed)
            if compressed:
                fn = make_compressed_train_step(model, opt_cfg, rules)
            else:
                fn = make_train_step(model, opt_cfg, rules)
            fn = jax.jit(fn)
            data = SyntheticLMData(cfg, 8, 32)
            with mesh:
                losses = []
                for _ in range(12):
                    params, opt, m = fn(params, opt, data.next_batch())
                    losses.append(float(m["loss"]))
            return losses

        lc = run(True)
        lu = run(False)
        print(json.dumps({"compressed": lc, "plain": lu}))
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", prog], env=env, cwd=os.path.
                         dirname(os.path.dirname(os.path.abspath(__file__))),
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    lc, lu = res["compressed"], res["plain"]
    assert lc[-1] < lc[0], "compressed training did not reduce loss"
    assert abs(lc[-1] - lu[-1]) < 0.35, (lc[-1], lu[-1])


def test_ep_moe_multidevice_subprocess():
    """shard_map expert parallelism on 4 fake devices matches the local
    executor bit-for-bit-ish."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import numpy as np, jax, jax.numpy as jnp, json
        from jax.sharding import Mesh
        from repro.configs.archs import smoke_config
        from repro.models import moe
        from repro.parallel.axes import ShardingRules, use_rules

        cfg = smoke_config("qwen3-moe-30b-a3b").with_(moe_impl="ep",
                                                      n_experts=8, top_k=2)
        p = moe.init_moe(jax.random.key(0), cfg, jnp.float32)
        x = jax.random.normal(jax.random.key(1), (4, 8, cfg.d_model))
        y_local, aux_l = moe.moe_ffn(p, cfg.with_(moe_impl="local"), x)

        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 2),
                    ("data", "model"))
        rules = ShardingRules(mesh=mesh, rules={"batch": "data"},
                              dp_axes=("data",), ep_axis="model",
                              tp_axis="model")
        with mesh, use_rules(rules):
            y_ep, aux_e = jax.jit(lambda p, x: moe.moe_ffn(p, cfg, x))(p, x)
        err = float(jnp.max(jnp.abs(y_ep - y_local)))
        print(json.dumps({"err": err, "aux_l": float(aux_l),
                          "aux_e": float(aux_e)}))
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", prog], env=env, cwd=os.path.
                         dirname(os.path.dirname(os.path.abspath(__file__))),
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["err"] < 5e-4, res
    # per-shard aux (pmean of local Switch estimators) is a different but
    # consistent estimator of the global one — same scale, not identical
    assert res["aux_e"] > 0
    assert abs(res["aux_l"] - res["aux_e"]) / res["aux_l"] < 0.25, res


def test_compressed_training_dp_tp_mesh_subprocess():
    """int8-EF gradient reduction composes with tensor parallelism via
    partial-manual shard_map (manual over DP, auto over model)."""
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
        from repro.training.steps import (init_opt_state, make_train_step,
                                          make_compressed_train_step)
        from repro.data.pipeline import SyntheticLMData

        cfg = smoke_config("yi-6b")
        model = LM(cfg)
        mesh = Mesh(np.asarray(jax.devices()).reshape(2, 2),
                    ("data", "model"))
        rules = default_rules(mesh)
        opt_cfg = AdamWConfig(lr=1e-3, total_steps=10, warmup_steps=2)

        def run(compressed):
            params = model.init(jax.random.key(0))
            specs = sharding.param_specs(params, mesh)
            params = jax.tree.map(
                lambda a, s: jax.device_put(a, NamedSharding(mesh, s)),
                params, specs)
            opt = init_opt_state(params, compressed=compressed)
            builder = (make_compressed_train_step if compressed
                       else make_train_step)
            fn = jax.jit(builder(model, opt_cfg, rules))
            data = SyntheticLMData(cfg, 8, 32)
            with mesh:
                losses = []
                for _ in range(10):
                    params, opt, m = fn(params, opt, data.next_batch())
                    losses.append(float(m["loss"]))
            return losses

        lc, lu = run(True), run(False)
        print(json.dumps({"c": lc, "u": lu}))
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", prog], env=env, cwd=os.path.
                         dirname(os.path.dirname(os.path.abspath(__file__))),
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["c"][-1] < res["c"][0]
    assert abs(res["c"][-1] - res["u"][-1]) < 0.3, res
