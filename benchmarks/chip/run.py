"""One run of one chip-benchmark cell.

  python benchmarks/chip/run.py --workload dsq-1.5b.reason --seed 7 \
      --seconds 30 --trace 0

From the root of a checkout: reads the cell from BENCHMARK.json and the
files under benchmarks/chip/, sets up, measures for ``--seconds``, checks
the served tokens against the plain reference and prints one JSON object
as the last line of standard output (``--trace 0``: the cell's end-to-end
metrics; ``--trace 1``: its per-layer metrics, read from a profiler trace
of the window's last seconds). The numbers compared for ``correct`` are the
last lines of standard error. Exits non-zero, with no result, when JAX finds
no TPU or fewer chips than the cell asks for, or when the checkout lacks
the program under test.
"""
import time

T0 = time.perf_counter()      # set-up is timed from process start

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True,
                    help="a cell name from BENCHMARK.json's workloads")
    ap.add_argument("--seed", type=int, required=True,
                    help="seed of the weights and the traffic")
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="1: per-layer metrics from a profiler trace")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")

    if not (CHECKOUT / "src" / "repro").is_dir():
        print(f"chip benchmark: no program under {CHECKOUT / 'src'}; run "
              f"from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(CHECKOUT / "src"))
    sys.path.insert(0, str(HERE))
    from chipbench import harness, registry

    try:
        cell = registry.load_cell(args.workload)
    except registry.RegistryError as e:
        print(f"chip benchmark: {e}", file=sys.stderr)
        return 2

    harness.enable_cache(CHECKOUT)
    try:
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), t0=T0)
    except harness.Refused as e:
        print(f"chip benchmark: {e}; nothing was measured", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
