"""Find the highest rate an open-loop cell sustains, in one process on the
chip:

    python3 chipbench/sweep.py --workload <cell> --seconds 10 \
        --rates 40,60,80,100

Builds the cell's service once (its system's ``build``, ``make_inputs``
and ``serve``, as ``systems/whisper_frontend.py`` has them), then offers
each rate for ``--seconds`` and prints one JSON line per rate: latency
percentiles, and whether the backlog grew (the last fifth of requests
waited over twice as long as the first fifth).  The knee is the highest
rate whose backlog did not grow; a cell's mix offers a fixed share of
it.  The benchmark's own runs never run this.
"""
import json
import sys

import run as runmod  # sets the caches and the import path before jax


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    runmod.setup_caches()
    import numpy as np

    from chipbench import bench, systems, traffic
    cell = bench.load_cell(args.workload)
    system = systems.load(cell.config["system"])
    bench.device_info(cell.chips)
    serve = system.build(cell, args.seed)
    inputs = system.make_inputs(cell, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate_per_s=rate)
        schedule = traffic.open_loop_schedule(mix, args.seed, args.seconds)
        done = system.serve(serve, inputs, schedule, [],
                            bench.Window(False, args.seconds))
        lat = done.latency_s * 1e3
        fifth = max(1, len(lat) // 5)
        first, last = lat[:fifth].mean(), lat[-fifth:].mean()
        print(json.dumps({
            "rate_per_s": rate, "requests": len(lat),
            "served_per_s": len(lat) / done.seconds,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "first_fifth_ms": float(first), "last_fifth_ms": float(last),
            "backlog_grew": bool(last > 2 * first),
            "late_p99_ms": float(np.percentile(done.late_s * 1e3, 99))}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
