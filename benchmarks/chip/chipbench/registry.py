"""Find a cell's parts by name.

``BENCHMARK.json`` at the checkout root names each cell's configuration,
traffic mix and chips, and lists the metrics. Everything else is one file
per name under the benchmark directory, so a cell, configuration, traffic
mix or metric is added by adding files:

  configs/<config>.json        the model configuration as it is run
  references/<model_type>.py   that architecture's plain reference
  traffic/<traffic>.json       the traffic mix's parameters
  cells/<cell>.json            the cell's engine sizes and check limits
  metrics/<metric>.py          one reader per metric: ``read(run)``
  peaks.json                   chip peaks keyed by ``device_kind``
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
CHECKOUT = BENCH_DIR.parents[1]


class RegistryError(Exception):
    """A name that BENCHMARK.json or the benchmark directory lacks."""


@dataclasses.dataclass
class Cell:
    """One entry of BENCHMARK.json's ``workloads`` with its files read."""
    name: str
    chips: int
    config: dict
    traffic: dict
    engine: dict
    check: dict
    end_to_end: list
    per_layer: list
    bench_dir: pathlib.Path


def read_json(path) -> dict:
    path = pathlib.Path(path)
    if not path.is_file():
        raise RegistryError(f"missing {path}")
    return json.loads(path.read_text())


def _reports(metric: dict, cell: str, end_to_end: set) -> bool:
    """Whether ``cell`` reports ``metric``: its ``workloads`` list, or
    without one, every cell (end to end) or every cell that reports the
    end-to-end metric it moves (per layer)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in end_to_end


def load_cell(name: str, bench_json=None, bench_dir=None) -> Cell:
    bench_dir = pathlib.Path(bench_dir or BENCH_DIR)
    bench = read_json(bench_json or CHECKOUT / "BENCHMARK.json")
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise RegistryError(f"no workload {name!r} in BENCHMARK.json "
                            f"(known: {', '.join(sorted(entries))})")
    entry = entries[name]
    spec = read_json(bench_dir / "cells" / f"{name}.json")
    traffic = read_json(bench_dir / "traffic" / f"{entry['traffic']}.json")
    traffic.update(spec.get("traffic", {}))
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, names)]
    return Cell(
        name=name, chips=int(entry["chips"]),
        config=read_json(bench_dir / "configs" / f"{entry['config']}.json"),
        traffic=traffic,
        engine=dict(spec["engine"]), check=dict(spec["check"]),
        end_to_end=e2e, per_layer=per_layer, bench_dir=bench_dir)


def _load_module(path: pathlib.Path, prefix: str):
    if not path.is_file():
        raise RegistryError(f"missing {path}")
    mod_name = prefix + "".join(c if c.isalnum() else "_" for c in path.stem)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(bench_dir, name: str):
    """``read(run) -> float | None`` of ``metrics/<name>.py``."""
    path = pathlib.Path(bench_dir) / "metrics" / f"{name}.py"
    return _load_module(path, "chipbench_metric_").read


def reference(bench_dir, model_type: str):
    """The module ``references/<model_type>.py``."""
    path = pathlib.Path(bench_dir) / "references" / f"{model_type}.py"
    return _load_module(path, "chipbench_reference_")


def peaks(bench_dir, device_kind: str) -> dict:
    """Peaks of one chip of ``device_kind``; an unknown kind is an error."""
    table = read_json(pathlib.Path(bench_dir) / "peaks.json")["devices"]
    if device_kind not in table:
        raise RegistryError(f"no peaks for device kind {device_kind!r} in "
                            f"peaks.json (known: {', '.join(sorted(table))})")
    return table[device_kind]
