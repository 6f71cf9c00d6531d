"""The precision control: the configuration's reference put in the
program's place, computed in float8 (the step below the bfloat16 the
configurations state), and compared with the float32 reference exactly
as a run compares the program.  Its numbers set the upper reading of each
limit; a sound limit fails it."""
from __future__ import annotations

from typing import Dict

import jax

from chipbench import bench, compare, convnet, traffic

CONTROL = "fp8"


def values(cell: bench.Cell, seed: int, seconds: float) -> Dict[str, float]:
    """The control's numbers at the cell's own sizes, on the inputs a run
    with this seed and window length would check."""
    cfg, tr = cell.config, cell.traffic
    ref = bench.reference_of(cfg)
    if tr["work"] == "forward":
        stages = cfg["stages"]
        params = convnet.make_params(bench.key(seed, 0), stages, cfg["dtype"])
        xs = convnet.make_batches(bench.key(seed, 1), stages, tr["batch"],
                                  tr["batches_held"], cfg["dtype"])[0]
        f = jax.jit(lambda p, x, r: ref.forward(p, x, stages, r),
                    static_argnums=2)
        want, got = f(params, xs, None), f(params, xs, CONTROL)
        return {f"fwd.{st['layer']}": compare.rel_l2(g, w)
                for st, g, w in zip(stages, got, want)}
    if tr["work"] == "train":
        stages = cfg["stages"]
        params = convnet.make_params(bench.key(seed, 0), stages,
                                     jax.numpy.float32)
        batches = convnet.make_batches(
            bench.key(seed, 1), stages, tr["batch"], tr["batches_held"],
            cfg["dtype"], targets=True)[:tr["checked_steps"]]
        want = ref.train(params, batches, tr["optimizer"], stages)
        got = ref.train(params, batches, tr["optimizer"], stages, CONTROL)
        return compare.train_checks(got, want)
    if tr["work"] == "serve":
        from chipbench import frontend
        schedule = traffic.open_loop_schedule(tr, seed, seconds)
        checked = traffic.checked_sample(schedule, tr["checked_requests"],
                                         seed)
        inputs = frontend.make_inputs(cell, seed)
        w1, w2 = ref.weights(bench.key(seed, 0), cfg)
        f = jax.jit(ref.frontend, static_argnums=3)
        worst = 0.0
        for i in checked:
            r = schedule[i]
            mel = inputs[r.windows][r.slot]
            worst = max(worst, compare.rel_l2(f(mel, w1, w2, CONTROL),
                                              f(mel, w1, w2, None)))
        return {"serve.rel_l2": worst}
    raise ValueError(f"no control for work {tr['work']!r}")
