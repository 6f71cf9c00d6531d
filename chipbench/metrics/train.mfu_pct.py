"""train.mfu_pct: the operations a training step requires per image
(forward, kernel gradients, input gradients where the input is a layer's
output; recomputation not counted) times the images per second of the
traced window, over the peak of all chips used."""
from chipbench import yardstick


def read(run, trace, device, config, traffic):
    if not run.work.get("steps"):
        return None
    rate = run.work["images"] / run.window_s
    peak = device["count"] * yardstick.peak_flops(device["kind"],
                                                  run.work["dtype"])
    return 100.0 * run.work["flops_per_image"] * rate / peak
