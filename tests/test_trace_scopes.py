"""The names the program gives its device work (``TRACE_SCOPES``) reach
the ``op_name`` metadata of the compiled ops they should cover, which is
where a profiler trace reads each device op's owner.

Programs are compiled on the CPU, the Pallas kernels in interpret mode:
a ``conv2d(plan=)`` forward on ``mec_fused``, a training step (``jax.grad``
through the MEC custom VJP, then AdamW), the conv1d kernel, a tiny
ResNet's training step (``repro.models.resnet``) and a tiny ConvNeXt's
(``repro.models.convnext``).
"""
import re

import pytest

import jax
import jax.numpy as jnp

from repro.core.conv_api import TRACE_SCOPES, conv2d, conv2d_spec
from repro.kernels.ops import mec_conv1d_tpu
from repro.models import convnext, resnet
from repro.optim import adamw
from repro.plan import ConvPlan

BF16 = jnp.bfloat16


def _chain(x, ws, plans):
    for w, (stride, plan) in zip(ws, plans):
        x = jax.nn.relu(conv2d(x, w, stride=stride, padding=1, plan=plan))
    return x


def _programs():
    """Name -> (function, example arguments)."""
    x = jnp.ones((2, 11, 11, 8), BF16)
    ws = [jnp.ones((3, 3, 8, 8), jnp.float32) for _ in range(2)]
    plans, shape = [], x.shape
    for stride in (2, 1):
        spec = conv2d_spec(jax.ShapeDtypeStruct(shape, BF16),
                           jax.ShapeDtypeStruct((3, 3, 8, 8), BF16),
                           stride=stride, padding=1)
        plans.append((stride, ConvPlan(spec, "bfloat16", "mec_fused",
                                       w_blk=spec.o_w)))
        shape = (2, spec.o_h, spec.o_w, 8)

    def forward(x, ws):
        return _chain(x, [w.astype(BF16) for w in ws], plans)

    def step(ws, state, x):
        def loss(ws):
            y = forward(x, ws).astype(jnp.float32)
            return jnp.mean(jnp.square(y))
        grads = jax.grad(loss)(ws)
        ws, state, _ = adamw.update(adamw.AdamWConfig(), grads, state, ws)
        return ws, state

    # One block per stage, so every stage after the first projects its
    # shortcut.
    net = {"image_size": 32, "in_channels": 3, "num_classes": 10,
           "stem": {"width": 4, "kernel": 7, "stride": 2, "pad": 3,
                    "pool": {"kernel": 3, "stride": 2, "pad": 1}},
           "depths": [1, 1, 1, 1], "widths": [4, 8, 16, 32], "expansion": 4}
    net_params, net_stats = resnet.init(jax.random.key(0), net)
    net_step = resnet.train_step(resnet.plan(net, 2, BF16),
                                 adamw.AdamWConfig())
    # One block per stage at a sixteenth of ConvNeXt-B's widths.
    cnx = {"image_size": 32, "in_channels": 3, "num_classes": 10,
           "stem": {"kernel": 4, "stride": 4},
           "downsample": {"kernel": 2, "stride": 2}, "depths": [1, 1, 1, 1],
           "dims": [8, 16, 32, 64], "kernel": 7, "expansion": 4,
           "ln_eps": 1e-6, "drop_path": 0.5, "layer_scale_init": 0.5}
    cnx_params = convnext.init(jax.random.key(0), cnx)
    cnx_step = convnext.train_step(convnext.plan(cnx, 2, BF16),
                                   adamw.AdamWConfig())

    return {
        "forward": (forward, (x, ws)),
        "train": (step, (ws, adamw.init(ws), x)),
        "conv1d": (mec_conv1d_tpu, (jnp.ones((2, 16, 8)),
                                    jnp.ones((3, 8)))),
        "resnet": (net_step, (net_params, net_stats,
                              adamw.init(net_params),
                              jnp.ones((2, 32, 32, 3), BF16),
                              jnp.zeros((2,), jnp.int32))),
        "convnext": (cnx_step, (cnx_params, adamw.init(cnx_params),
                                jnp.ones((2, 32, 32, 3), BF16),
                                jnp.zeros((2,), jnp.int32),
                                jax.random.key(1))),
    }


# scope -> (program, what it covers: the JAX primitives, the last
# component of an op's name stack, of which one at least must carry it)
COVERS = {
    "conv2d": ("forward", {"dot_general"}),
    "conv2d_pad": ("forward", {"pad"}),
    "mec_fold": ("forward", {"pad", "reshape", "slice"}),
    "conv2d_out": ("forward", {"slice", "convert_element_type"}),
    "mec_input_grad": ("train", {"dot_general"}),
    "mec_weight_grad": ("train", {"dot_general"}),
    "adamw_update": ("train", {"sqrt"}),
    "mec_fused": ("forward", {"dot_general"}),
    "mec_fused_wgrad": ("train", {"dot_general"}),
    "mec_conv1d": ("conv1d", {"mul"}),
    "batch_norm": ("resnet", {"rsqrt"}),
    "pointwise": ("resnet", {"conv_general_dilated"}),
    "head": ("resnet", {"dot_general"}),
    "depthwise": ("convnext", {"conv_general_dilated"}),
    "layer_norm": ("convnext", {"rsqrt"}),
}
# Scopes that lie inside ``conv2d`` wherever they appear.
IN_CONV2D = ("conv2d_pad", "mec_fold", "conv2d_out", "mec_input_grad",
             "mec_weight_grad", "mec_fused", "mec_fused_wgrad")


def _unwrap(component):
    """``transpose(jvp(conv2d))`` -> ``conv2d``."""
    m = re.match(r"^[\w.\-]+\((.*)\)$", component)
    return _unwrap(m.group(1)) if m else component


@pytest.fixture(scope="module")
def op_names():
    """Program -> the name stacks (as lists of unwrapped components) of
    its compiled HLO's ops."""
    out = {}
    for name, (fn, args) in _programs().items():
        text = jax.jit(fn).lower(*args).compile().as_text()
        out[name] = [[_unwrap(c) for c in n.split("/")]
                     for n in set(re.findall(r'op_name="([^"]*)"', text))]
    return out


def test_scopes_are_declared_once():
    assert set(TRACE_SCOPES) == set(COVERS)
    assert len(set(TRACE_SCOPES)) == len(TRACE_SCOPES)


@pytest.mark.parametrize("scope", TRACE_SCOPES)
def test_scope_names_its_ops(op_names, scope):
    program, primitives = COVERS[scope]
    covered = [stack for stack in op_names[program] if scope in stack]
    assert covered, f"no op of {program} carries {scope}"
    assert {stack[-1] for stack in covered} & primitives, \
        (scope, sorted({stack[-1] for stack in covered}))
    if scope in IN_CONV2D:
        assert all(stack.index("conv2d") < stack.index(scope)
                   for stack in covered)


def test_training_scopes_are_apart(op_names):
    """The backward's halves and the optimizer do not nest in each
    other, the optimizer lies outside every conv, and the weight
    gradient's kernel inside ``mec_weight_grad``."""
    for stack in op_names["train"]:
        owned = {"mec_input_grad", "mec_weight_grad", "adamw_update"} & \
            set(stack)
        assert len(owned) <= 1, stack
        if "adamw_update" in stack:
            assert "conv2d" not in stack
        if "mec_fused_wgrad" in stack:
            assert "mec_weight_grad" in stack[:stack.index("mec_fused_wgrad")]


def test_resnet_scopes_are_apart(op_names):
    """A 1x1 conv's convolutions, forward and backward, lie in
    ``pointwise`` and then ``conv2d``; no batch norm or head op lies in a
    conv."""
    for stack in op_names["resnet"]:
        if "pointwise" in stack and stack[-1] == "conv_general_dilated":
            assert "conv2d" in stack[stack.index("pointwise"):], stack
        if {"batch_norm", "head"} & set(stack):
            assert "conv2d" not in stack and "pointwise" not in stack, stack


def test_convnext_scopes_are_apart(op_names):
    """A depthwise conv's convolutions, forward and backward, lie in
    ``depthwise`` and then ``conv2d``, never in ``pointwise``; no layer
    norm op lies in a conv."""
    convs = [stack for stack in op_names["convnext"]
             if stack[-1] == "conv_general_dilated"]
    assert any("depthwise" in stack for stack in convs)
    for stack in convs:
        if "depthwise" in stack:
            assert "conv2d" in stack[stack.index("depthwise"):], stack
            assert "pointwise" not in stack, stack
    for stack in op_names["convnext"]:
        if "layer_norm" in stack:
            assert "conv2d" not in stack and "depthwise" not in stack, stack
