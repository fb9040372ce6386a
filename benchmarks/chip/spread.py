"""Spread of each metric over sets of runs, for setting bounds.

  python benchmarks/chip/spread.py set1.jsonl set2.jsonl

Each file holds the result lines (the last stdout line of ``run.py``) of
one set of runs of one cell. For each metric it prints every set's median
and spread (the distance between the first and third quartile of
``statistics.quantiles(values, n=4)``, as a share of the median), the wider
of the sets' spreads, five times it, and whether every run was correct.
"""
import json
import pathlib
import statistics
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))
from chipbench.stats import spread  # noqa: E402


def load(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip().startswith("{")]


def main(paths):
    sets = [load(p) for p in paths]
    names = sorted({m for runs in sets for r in runs for m in r["metrics"]})
    print(f"runs per set: {[len(r) for r in sets]}; all correct: "
          f"{all(r['correct'] for runs in sets for r in runs)}")
    for name in names:
        meds, spreads = [], []
        for runs in sets:
            vals = [r["metrics"][name]["value"] for r in runs
                    if name in r["metrics"]]
            meds.append(statistics.median(vals))
            spreads.append(spread(vals))
        widest = max(spreads)
        print(f"{name}: medians {meds} spreads "
              f"{[round(s, 5) for s in spreads]} widest {widest:.5f} "
              f"x5 {5 * widest:.5f}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
