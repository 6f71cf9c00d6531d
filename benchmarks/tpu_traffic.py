"""TPU HBM-traffic model for the MEC Pallas kernel (DESIGN.md §2).

The kernel-level win is reported as modeled HBM bytes, per cv layer,
f32:

  im2col : read I + write L_i2c + read L_i2c + write O
  mec    : read I + write L_mec + read (o_h*k_h rows of L) + write O
  fused  : read I * (1 + halo/rows a step) + write O   (no L at all)

``fused`` is the ``mec_fused`` kernel, modeled at 8 output rows a grid
step; ``mec`` is the paper's data flow, with L in HBM.  Arithmetic
intensity (FLOPs/HBM byte) against the v5e ridge point (197e12/819e9 =
241 FLOP/B) says whether the layer stays memory-bound.
"""
from __future__ import annotations

import json

from repro.bench.scenarios import CV_LAYERS, layer_spec as spec
from repro.core.memory import conv_flops, im2col_overhead, mec_overhead
from repro.launch.hlo_analysis import HBM_BW, PEAK_FLOPS

RIDGE = PEAK_FLOPS / HBM_BW


def traffic(s):
    f32 = 4
    i_bytes = s.i_n * s.i_h * s.i_w * s.i_c * f32
    o_bytes = s.i_n * s.o_h * s.o_w * s.k_c * f32
    k_bytes = s.k_h * s.k_w * s.i_c * s.k_c * f32
    l_i2c = im2col_overhead(s) * f32
    l_mec = mec_overhead(s) * f32
    gemm_reads = s.i_n * s.o_h * s.k_h * s.o_w * s.k_w * s.i_c * f32
    # oh_blk output rows per grid step + (k_h - s_h)-row halo
    oh_blk = 8
    halo_factor = 1 + max(s.k_h - s.s_h, 0) / (oh_blk * s.s_h)
    return {
        "im2col": i_bytes + l_i2c + l_i2c + k_bytes + o_bytes,
        "mec": i_bytes + l_mec + gemm_reads + k_bytes + o_bytes,
        "fused": i_bytes * halo_factor + k_bytes + o_bytes,
    }


def rows(batch: int = 32):
    out = []
    for name in CV_LAYERS:
        s = spec(name, batch=batch)
        t = traffic(s)
        flops = conv_flops(s)
        out.append({"name": name, "flops": flops,
                    "ai_flop_per_byte": flops / t["fused"],
                    "bound": "compute" if flops / t["fused"] > RIDGE
                             else "memory", **t})
    return out


def main(emit=print, fmt: str = "csv"):
    if fmt == "json":
        out = rows()
        emit(json.dumps(out, indent=2))
        return out
    emit("table,name,us_per_call,derived")
    for name in CV_LAYERS:
        s = spec(name, batch=32)     # server batch
        t = traffic(s)
        flops = conv_flops(s)
        ai = flops / t["fused"]
        t_mem_us = t["fused"] / HBM_BW * 1e6
        t_cmp_us = flops / PEAK_FLOPS * 1e6
        emit(f"tpu_traffic,{name},{max(t_mem_us, t_cmp_us):.1f},"
             f"im2col={t['im2col']/2**20:.1f}MB;"
             f"mec={t['mec']/2**20:.1f}MB;"
             f"fused={t['fused']/2**20:.1f}MB;"
             f"fused_vs_im2col={t['im2col']/t['fused']:.2f}x;"
             f"AI={ai:.0f}FLOP/B;"
             f"bound={'compute' if ai > RIDGE else 'memory'}")
    return None


if __name__ == "__main__":
    main()
