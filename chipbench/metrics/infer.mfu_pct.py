"""infer.mfu_pct: forward operations per image (from shapes) times the
images per second of the traced window, over the chip's peak."""
from chipbench import yardstick


def read(run, trace, device, config, traffic):
    if not run.work.get("forwards"):
        return None
    rate = run.work["images"] / run.window_s
    peak = yardstick.peak_flops(device["kind"], run.work["dtype"])
    return 100.0 * run.work["flops_per_image"] * rate / peak
