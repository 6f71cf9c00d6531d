"""Direct tests for repro.launch.hlo_analysis: collective-bytes parsing
(async pairs, iota vs explicit replica_groups, tuple-typed -start) and
the no-silent-dtype-default contract of _shape_bytes."""
import pytest

from repro.launch.hlo_analysis import (_shape_bytes, collective_bytes)


def test_shape_bytes_known_dtypes():
    assert _shape_bytes("f32", "8,128") == 8 * 128 * 4
    assert _shape_bytes("bf16", "2,3,4") == 24 * 2
    assert _shape_bytes("pred", "16") == 16
    assert _shape_bytes("c128", "2") == 32
    assert _shape_bytes("f8e4m3fn", "64") == 64
    assert _shape_bytes("f4e2m1fn", "64") == 64      # packed-byte floor
    assert _shape_bytes("token", "") == 0
    assert _shape_bytes("f32", "") == 4              # scalar


def test_shape_bytes_unknown_dtype_raises():
    """The PR-4-era silent 4-byte default is gone: an unknown dtype must
    fail loudly, not mis-count collective/memaudit bytes invisibly."""
    with pytest.raises(ValueError, match="unknown HLO dtype 'f6e3m2fn'"):
        _shape_bytes("f6e3m2fn", "8,8")


def test_collective_bytes_sync_ops_iota_groups():
    hlo = "\n".join([
        "  %ag = f32[8,128]{1,0} all-gather(f32[2,128] %p), "
        "replica_groups=[4,4]<=[16], dimensions={0}",
        "  %ar = f32[4,64]{1,0} all-reduce(f32[4,64] %q), "
        "replica_groups=[2,8]<=[16], to_apply=%add",
        "  %rs = f32[2,128]{1,0} reduce-scatter(f32[8,128] %r), "
        "replica_groups=[4,4]<=[16], dimensions={0}",
    ])
    out = collective_bytes(hlo)
    # all-gather operand = result / group_size
    assert out["all-gather"] == 8 * 128 * 4 // 4
    # all-reduce moves result-sized operands
    assert out["all-reduce"] == 4 * 64 * 4
    # reduce-scatter operand = result * group_size
    assert out["reduce-scatter"] == 2 * 128 * 4 * 4
    assert out["count"] == 3
    assert out["total"] == sum(
        out[k] for k in ("all-gather", "all-reduce", "reduce-scatter"))


def test_collective_bytes_explicit_groups_match_iota():
    """{{0,1,2,3}} and [4,4]<=[16] describe the same group size — the
    accounting must not depend on which form the dump printed."""
    iota = ("  %ag = f32[8,128]{1,0} all-gather(f32[2,128] %p), "
            "replica_groups=[4,4]<=[16]")
    expl = ("  %ag = f32[8,128]{1,0} all-gather(f32[2,128] %p), "
            "replica_groups={{0,1,2,3},{4,5,6,7}}")
    assert collective_bytes(iota) == collective_bytes(expl)


def test_collective_bytes_async_pair_counted_once():
    """-start/-done pairs are one logical collective: bytes and count
    come from the -start line only."""
    hlo = "\n".join([
        "  %ags = (f32[2,128]{1,0}, f32[8,128]{1,0}) "
        "all-gather-start(f32[2,128] %p), replica_groups=[4,4]<=[16]",
        "  %agd = f32[8,128]{1,0} all-gather-done("
        "(f32[2,128], f32[8,128]) %ags)",
    ])
    out = collective_bytes(hlo)
    assert out["count"] == 1
    # tuple-typed -start: the RESULT half of (operand, result) is what
    # the wire moves — 8*128*4 / group 4
    assert out["all-gather"] == 8 * 128 * 4 // 4


def test_collective_bytes_tpu_permute_start_sync_scalars():
    """A TPU collective-permute-start tuple carries two u32[] sync
    scalars after (operand, result); only the result is data."""
    hlo = "\n".join([
        "  %collective-permute-start = (bf16[32,5,224,64]{2,3,1,0:T(8,128)"
        "(2,1)S(1)}, bf16[32,5,224,64]{2,3,1,0:T(8,128)(2,1)S(1)}, "
        "u32[]{:S(2)}, u32[]{:S(2)}) collective-permute-start(%slice.6), "
        "channel_id=1, source_target_pairs={{1,0},{2,1},{3,2}}",
        "  %collective-permute-done = bf16[32,5,224,64]{2,3,1,0} "
        "collective-permute-done(%collective-permute-start)",
    ])
    out = collective_bytes(hlo)
    assert out["count"] == 1
    assert out["collective-permute"] == 32 * 5 * 224 * 64 * 2


def test_collective_bytes_permute_and_all_to_all():
    hlo = "\n".join([
        "  %cp = bf16[4,256]{1,0} collective-permute(bf16[4,256] %p), "
        "source_target_pairs={{0,1},{1,0}}",
        "  %a2a = f32[16,16]{1,0} all-to-all(f32[16,16] %q), "
        "replica_groups={{0,1,2,3}}, dimensions={0}",
    ])
    out = collective_bytes(hlo)
    assert out["collective-permute"] == 4 * 256 * 2
    assert out["all-to-all"] == 16 * 16 * 4
    assert out["count"] == 2


def test_collective_bytes_empty_and_non_collective_lines():
    hlo = "  %m = f32[8,8]{1,0} multiply(f32[8,8] %a, f32[8,8] %b)"
    out = collective_bytes(hlo)
    assert out["count"] == 0 and out["total"] == 0
