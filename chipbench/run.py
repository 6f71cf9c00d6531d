"""Run one cell of BENCHMARK.json once, on the chips of this machine.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up (planning, weights and inputs from the seed, compiling or loading
every program the cell's traffic uses) runs first; then the measured
window of ``--seconds``; then the check of what the window produced
against the configuration's plain reference.  With ``--trace 0`` the
result reports the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.

The last line of stdout is one JSON object; the checks, each number
beside its limit, are the last lines of stderr.  Without a TPU, or with
fewer chips than the cell asks for, it exits 2 and prints no result.
"""
import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
# JAX's persistent compile cache and the program's plan cache stay inside
# the checkout, at fixed paths (the cache key holds the path).
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
os.environ["REPRO_PLAN_CACHE_DIR"] = str(ROOT / ".plan_cache")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def seed_arg(text: str) -> int:
    value = int(text)
    if value < 0:
        raise argparse.ArgumentTypeError("the seed is a whole number >= 0")
    return value


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=seed_arg, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    return ap.parse_args(argv)


def setup_caches() -> None:
    """The persistent compile cache at the path set above, holding every
    program, however quickly it compiled, so a second run compiles
    nothing."""
    import jax
    from repro.core.compat import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def main(argv=None) -> int:
    args = parse(argv)
    setup_caches()
    from chipbench import bench
    cell = bench.load_cell(args.workload)
    try:
        line, run = bench.execute(cell, args.seed, args.seconds,
                                  bool(args.trace), STARTED)
    except bench.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    bench.emit(line, run)
    return 0


if __name__ == "__main__":
    sys.exit(main())
