"""The readings that set a cell's limits, in one process on the chip:

    python3 chipbench/readings.py --workload <cell> --seconds 2 \
        --seeds 1,2,...  [--control-seeds 1,2,3] \
        [--fault half_batch --fault-seeds 1,2,3]

For each seed of ``--seeds``, a whole run of the cell (short window, the
cell's own load) and its compared numbers: the lower readings.  For each
of ``--control-seeds``, the precision control's numbers (``control.py``):
the upper readings.  With ``--fault``, runs with that fault of the
cell's system planted (its ``FAULTS``).  One JSON line per reading, then
a summary line: each number's largest program reading and smallest
control or fault reading.  The benchmark's own runs never run this.
"""
import json
import sys
import time

import run as runmod  # sets the caches and the import path before jax


def seeds(text: str):
    return [int(s) for s in text.split(",") if s]


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--seeds", type=seeds, default=[])
    ap.add_argument("--control-seeds", type=seeds, default=[])
    ap.add_argument("--fault")
    ap.add_argument("--fault-seeds", type=seeds, default=[])
    args = ap.parse_args(argv)

    runmod.setup_caches()
    from chipbench import bench, control, faults
    cell = bench.load_cell(args.workload)
    low, high = {}, {}

    def report(kind, seed, values, extra=None):
        print(json.dumps(dict({"kind": kind, "seed": seed, "values": values},
                              **(extra or {}))), flush=True)

    def program(seed):
        line, run = bench.execute(cell, seed, args.seconds, False,
                                  time.perf_counter())
        values = {c.name: c.value for c in run.checks}
        return values, {"correct": line["correct"],
                        "metrics": {k: v["value"]
                                    for k, v in line["metrics"].items()}}

    for seed in args.seeds:
        values, extra = program(seed)
        report("program", seed, values, extra)
        for k, v in values.items():
            low[k] = max(low.get(k, 0.0), v)
    for seed in args.control_seeds:
        values = control.values(cell, seed, args.seconds)
        report("control", seed, values)
        for k, v in values.items():
            high.setdefault("control", {})[k] = min(
                high.get("control", {}).get(k, float("inf")), v)
    for seed in args.fault_seeds:
        with faults.plant(args.fault, cell.config["system"]):
            values, extra = program(seed)
        report("fault:" + args.fault, seed, values, extra)
        for k, v in values.items():
            high.setdefault(args.fault, {})[k] = min(
                high.get(args.fault, {}).get(k, float("inf")), v)
    print(json.dumps({"kind": "summary", "lower": low, "upper": high}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
