"""Where the benchmark's data files are, by the names BENCHMARK.json uses."""

from __future__ import annotations

import importlib
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "cellbench")


def load_json(path: str) -> dict:
    with open(path if os.path.isabs(path) else os.path.join(ROOT, path)) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return load_json("BENCHMARK.json")


def find_cell(bench: dict, name: str) -> tuple[dict, dict]:
    """(workload entry, its configuration's entry); KeyError names the
    cells there are."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    cell = cells[name]
    cfg = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return cell, cfg


def traffic_file(traffic: str) -> str:
    return os.path.join(HERE, "traffic", f"{traffic}.json")


def metric_entries(bench: dict, cell: str, group: str) -> list[dict]:
    """The metrics of ``group`` ("end_to_end" / "per_layer") this cell
    reports: those with no ``workloads`` key, or that list the cell."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def metric_spec(group: str, name: str) -> dict:
    """The data entry of one metric: {"reader": ..., "params": {...}}.

    ``<base>-<cell tag>`` is the metric ``<base>`` in another cell (an
    entry of BENCHMARK.json names one end-to-end metric it moves, so a
    reader used in three cells has three entries): it reads
    ``<base>.json`` unless a file of its own name is there."""
    sub = "end_to_end" if group == "end_to_end" else "layers"
    for stem in (name, name.split("-", 1)[0]):
        path = os.path.join(HERE, sub, f"{stem}.json")
        if os.path.exists(path):
            return load_json(path)
    raise FileNotFoundError(f"no data entry for metric {name!r} under "
                            f"cellbench/{sub}/")


def generator(kind: str):
    return importlib.import_module(f"cellbench.generators.{kind}")


def reader(name: str):
    return importlib.import_module(f"cellbench.readers.{name}")
