"""Everything the harness finds by name: the manifest's cells, each
configuration's file, each traffic mix's file, each per-layer metric's
reader.  A later PR adds files and manifest entries; nothing here names
a cell, a configuration, a mix or a metric."""

from __future__ import annotations

import importlib.util
import json
import os

from benchmarks import loadgen

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def manifest() -> dict:
    return load_json(os.path.join(REPO, "BENCHMARK.json"))


def cell(name: str) -> dict:
    for c in manifest()["workloads"]:
        if c["name"] == name:
            return c
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> dict:
    """The configuration as it is run.  The manifest's `file` wins; a
    configuration not (yet) in the manifest is found by its name."""
    for c in manifest()["configs"]:
        if c["name"] == name:
            return load_json(os.path.join(REPO, c["file"]))
    return load_json(os.path.join(BENCH, "configs", name + ".json"))


def traffic(name: str) -> dict:
    """The mix as its file has it; `request_fields` that name what the
    body carries already are refused here, where the file is read."""
    mix = load_json(os.path.join(BENCH, "traffic", name + ".json"))
    loadgen.request_field_specs(mix)
    return mix


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip.  An unknown kind is an error, never
    a default."""
    table = load_json(os.path.join(BENCH, "peaks.json"))
    if device_kind not in table["devices"]:
        raise KeyError(
            f"device kind {device_kind!r} is not in benchmarks/peaks.json "
            f"(known: {sorted(table['devices'])}); add it with its source")
    return table["devices"][device_kind]


def _module(folder: str, name: str):
    """`benchmarks/<folder>/<name>.py`, loaded by its file's name (a
    metric's name may hold a dot)."""
    path = os.path.join(BENCH, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{folder}." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer_metric(name: str):
    """The reader module of one per-layer metric:
    `benchmarks/layer_metrics/<name>.py` with LAYER, UNIT, SOURCE, MOVES
    and `read(ctx) -> float | None`."""
    return _module("layer_metrics", name)


def needed_flops(plane: str):
    """The count of the operations a request NEEDS under one plane's
    family of models: `benchmarks/needed_flops/<plane>.py` with
    `matmul_weights(config) -> dict` and `request_flops(config, mix,
    prompt_len, got, fields) -> float`.  A later configuration whose
    plane is new adds a file; nothing here names one."""
    return _module("needed_flops", plane)


def metrics_for(cell_name: str, kind: str) -> list:
    """The manifest's `end_to_end` or `per_layer` entries this cell
    reports: those that list it, and those that list nothing and move
    (or are) a metric the cell reports."""
    m = manifest()
    e2e = [e for e in m["end_to_end"]
           if cell_name in e.get("workloads", [cell_name])]
    if kind == "end_to_end":
        return e2e
    mine = {e["name"] for e in e2e}
    return [p for p in m["per_layer"]
            if (cell_name in p["workloads"] if "workloads" in p
                else p["moves"] in mine)]
