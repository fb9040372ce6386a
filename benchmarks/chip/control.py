"""Readings for the limits of ``correct``: runs of one cell in one process.

  python benchmarks/chip/control.py --workload dsq-1.5b.reason \
      --seconds 20 --seeds 101 102 103 [--kv-dtype fp8] [--reference-fp8] \
      [--fault token|draft]

Runs the cell once per seed, exactly as ``run.py`` does, and prints one JSON
line per run with the numbers ``correct`` compares. Without options these
are the program's own readings (the lower end of each limit). The upper
ends: ``--kv-dtype fp8`` runs the program's float8 KV-cache path, the
control; ``--reference-fp8`` adds, under ``control``,
the same numbers for the reference with float8 weights put in the
program's place, read at the positions of the same sampled requests;
``--fault token`` alters every committed token where acceptance produces
it; ``--fault draft`` breaks the draft (its final norm negated, so it
proposes the tokens it ranks last). The benchmark's own runs take none of
these paths.
"""
import time

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


def alter_committed_tokens(vocab_size: int) -> None:
    """Greedy acceptance commits the next token of the vocabulary in place
    of the one it chose, in every step built from here on."""
    from repro.core import acceptance
    honest = acceptance.greedy_chain_accept

    def altered(logits, props):
        a, accepted, commit = honest(logits, props)
        return a, accepted, (commit + 1) % vocab_size
    acceptance.greedy_chain_accept = altered


def negated_final_norm(params):
    """The draft's weights: the target's, with the final norm's scale
    negated (one small new array; every other leaf is shared)."""
    return dict(params, final_norm={"scale": -params["final_norm"]["scale"]})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--kv-dtype", choices=("fp8",), default=None,
                    help="run the program's quantized KV-cache path")
    ap.add_argument("--reference-fp8", action="store_true",
                    help="also read the float8-weight reference control")
    ap.add_argument("--fault", choices=("token", "draft"), default=None,
                    help="plant a fault in the timed path")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(CHECKOUT / "src"))
    sys.path.insert(0, str(HERE))
    from chipbench import harness, registry

    harness.enable_cache(CHECKOUT)
    cell = registry.load_cell(args.workload)
    overrides = {"kv_dtype": args.kv_dtype} if args.kv_dtype else None
    draft_of = None
    if args.fault == "token":
        alter_committed_tokens(cell.config["vocab_size"])
    elif args.fault == "draft":
        draft_of = negated_final_norm
    for seed in args.seeds:
        result = harness.run_cell(cell, seed, args.seconds, False,
                                  t0=time.perf_counter(),
                                  engine_overrides=overrides,
                                  reference_control=args.reference_fp8,
                                  draft_of=draft_of)
        line = {"workload": cell.name, "seed": seed, "fault": args.fault,
                "kv_dtype": args.kv_dtype or cell.engine.get("kv_dtype"),
                "correct": result["correct"],
                "metrics": {k: v["value"]
                            for k, v in result["metrics"].items()},
                "checks": {k: v["value"]
                           for k, v in result["checks"].items()},
                "control": result.get("control"),
                "memory_peak_bytes": result["device"]["memory_peak_bytes"]}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
