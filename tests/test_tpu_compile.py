"""AOT compiles for a TPU v5e that is described, not attached.

The TPU compiler refuses what the Pallas interpreter accepts (unaligned
blocks, dynamic slices of loaded values, strided slices), so the main-path
kernel and the XLA MEC training program are compiled here at paper
Table-2 widths, batch 32, as the chip would compile them.  The topology
is described inside a fixture, never at import: only one process may
hold the TPU library, and every xdist worker imports this file.
"""
import os

import pytest

import jax
import jax.numpy as jnp

from repro.bench.scenarios import layer_spec
from repro.core.conv_api import conv2d

LAYERS = ("cv4", "cv9", "cv10", "cv11", "cv12")
DTYPES = ("bfloat16", "float32")
BATCH = 32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A described chip's executables are written to a compilation cache
    # but cannot be read back without the chip: keep the cache off here.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _operands(name, dtype, sharding):
    s = layer_spec(name, batch=BATCH)
    x = jax.ShapeDtypeStruct((s.i_n, s.i_h, s.i_w, s.i_c), jnp.dtype(dtype),
                             sharding=sharding)
    k = jax.ShapeDtypeStruct((s.k_h, s.k_w, s.i_c, s.k_c), jnp.dtype(dtype),
                             sharding=sharding)
    return s, x, k


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("name", LAYERS)
def test_mec_fused_compiles_for_v5e(one_chip, name, dtype):
    s, x, k = _operands(name, dtype, one_chip)
    compiled = jax.jit(lambda a, b: conv2d(
        a, b, stride=(s.s_h, s.s_w), algorithm="mec_fused",
        interpret=False)).lower(x, k).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("name", LAYERS)
def test_xla_mec_fwd_grad_compiles_for_v5e(one_chip, name, dtype):
    s, x, k = _operands(name, dtype, one_chip)

    def loss(a, b):
        y = conv2d(a, b, stride=(s.s_h, s.s_w), algorithm="mec")
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(
        x, k).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes + \
        mem.output_size_in_bytes < 16 << 30       # fits one v5e chip
    assert "tpu_custom_call" not in compiled.as_text()


def test_mosaic_kernel_carries_program_names(one_chip):
    """On the chip's compile the Mosaic kernel's ``op_name`` holds the
    ``conv2d`` scope and the kernel's ``pallas_call`` name, which is what
    a profiler trace reads as its owner."""
    s, x, k = _operands("cv12", "bfloat16", one_chip)
    text = jax.jit(lambda a, b: conv2d(
        a, b, stride=(s.s_h, s.s_w), padding=1, algorithm="mec_fused",
        interpret=False)).lower(x, k).compile().as_text()
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line and "op_name=" in line]
    assert kernels
    for line in kernels:
        name = line.split('op_name="', 1)[1].split('"', 1)[0]
        assert name.endswith("/conv2d/jit(mec_conv_fused_pallas)/mec_fused/"
                             "pallas_call"), name


# The geometries the whole ResNet-101 adds to ``LAYERS``, at batch 32,
# and resnet101_t3's cv4: (input h = w, input channels, kernel, output
# channels, stride, pad).
RESNET = {
    "cv4": (224, 64, 7, 64, 2, 0),
    "stem": (224, 3, 7, 64, 2, 3),
    "s2_3x3_56": (56, 128, 3, 128, 2, 1),
    "s2_3x3_28": (28, 256, 3, 256, 2, 1),
    "s2_3x3_14": (14, 512, 3, 512, 2, 1),
    "1x1_56": (56, 64, 1, 256, 1, 0),
    "1x1_s2_56": (56, 256, 1, 512, 2, 0),
    "s1_3x3_56": (56, 64, 3, 64, 1, 1),
    "s1_3x3_28": (28, 128, 3, 128, 1, 1),
    "s1_3x3_14": (14, 256, 3, 256, 1, 1),
    "s1_3x3_7": (7, 512, 3, 512, 1, 1),
}


@pytest.mark.parametrize("name", RESNET)
def test_resnet_geometry_trains_on_v5e_pick(one_chip, name):
    """Each geometry's forward and both gradients compile for the v5e on
    the algorithm the planner picks there, in bfloat16; a stride-1
    ``mec_fused`` conv's input gradient is a Mosaic kernel of its own,
    and so is the weight gradient of a ``mec_fused`` conv wherever
    ``fused_weight_grad_refusal`` takes it."""
    from repro.core.conv_api import conv2d_spec
    from repro.kernels.mec_conv import fused_weight_grad_refusal
    from repro.launch.costmodel import pick_conv2d_algorithm
    size, i_c, k, o_c, stride, pad = RESNET[name]
    dt = jnp.bfloat16
    x = jax.ShapeDtypeStruct((BATCH, size, size, i_c), dt, sharding=one_chip)
    w = jax.ShapeDtypeStruct((k, k, i_c, o_c), dt, sharding=one_chip)
    padding = pad or "VALID"
    spec = conv2d_spec(x, w, stride=stride, padding=padding)
    pick = pick_conv2d_algorithm(spec, backend="tpu", dtype="bfloat16")

    def loss(a, b):
        y = conv2d(a, b, stride=stride, padding=padding, algorithm=pick,
                   interpret=False)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, w).compile() \
        .as_text()
    assert ("tpu_custom_call" in text) == (pick == "mec_fused"), pick
    input_grad = [line for line in text.splitlines()
                  if "tpu_custom_call" in line and "mec_input_grad" in line]
    assert bool(input_grad) == (pick == "mec_fused" and stride == 1), name
    weight_grad = [line for line in text.splitlines()
                   if "tpu_custom_call" in line and "mec_weight_grad" in line]
    assert all("/mec_fused_wgrad/" in line for line in weight_grad)
    takes = fused_weight_grad_refusal(spec, "bfloat16") is None
    assert bool(weight_grad) == (pick == "mec_fused" and takes), name
    assert pick == ("direct" if k == 1 or i_c < 4 else "mec_fused"), pick
