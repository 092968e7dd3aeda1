#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration and its traffic mix are found by name through
``BENCHMARK.json``.  With ``--trace 0`` the result carries the cell's
end-to-end metrics; with ``--trace 1`` its per-layer metrics, the device's
busy and traced time, and a breakdown.  The run refuses (exit 2, no
result) where JAX finds no TPU or fewer chips than the cell needs, and
where the program's sources are not beside the benchmark.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def configure_compile_cache() -> str:
    """The program's fixed cache directory (``$JAX_COMPILATION_CACHE_DIR``
    where set), with every executable kept: the filter's many small
    compiles are each under JAX's default thresholds."""
    import jax

    from repro.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0 or args.seconds <= 0:
        ap.error("--seed must be >= 0 and --seconds > 0")

    sys.path.insert(0, HERE)
    import bench
    try:
        spec = bench.load_spec()
        cell, cfg, mix = bench.find_cell(spec, args.workload)
        if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
            raise bench.Refusal("the program (src/repro) is not beside "
                                "the benchmark")
        sys.path.insert(0, os.path.join(ROOT, "src"))
        bench.log(f"compile cache: {configure_compile_cache()}")
        device = bench.require_devices(int(cell["chips"]))
        bench.log(f"device: {device}")
        counter = bench.CompileCounter().install()
        out = bench.run_cell(spec, cell, cfg, mix, args.seed, args.seconds,
                             bool(args.trace), T_START, device, counter)
    except bench.Refusal as e:
        print(f"chipbench: refusing to run: {e}", file=sys.stderr)
        return 2
    for line in bench.format_checks(out["checks"]):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
