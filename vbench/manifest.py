"""BENCHMARK.json as the harness reads it: a cell's configuration, mix and
metrics, and every file found by the name the manifest gives."""

from __future__ import annotations

import importlib.util
import json
import os
import re


def load(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in manifest['workloads']]}")


def config(manifest: dict, root: str, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            with open(os.path.join(root, c["file"])) as f:
                return json.load(f)
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def metrics_of(manifest: dict, group: str, workload: str) -> list[dict]:
    """The metrics of ``group`` (end_to_end or per_layer) this cell
    reports: those that list it, and those that list no cells."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


def reader(root: str, name: str):
    """The ``read(run)`` of vbench/metrics/<name>.py."""
    path = os.path.join(root, "vbench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"vbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(root: str, device_kind: str) -> dict:
    """The device's row of the table of peaks: vbench/peaks/<kind>.json,
    the kind as JAX reports it with every other character made ``_``. An
    unknown device is an error, never a default."""
    name = re.sub(r"[^A-Za-z0-9.\-]", "_", device_kind)
    path = os.path.join(root, "vbench", "peaks", f"{name}.json")
    if not os.path.exists(path):
        raise KeyError(f"no peaks for device kind {device_kind!r}: add "
                       f"{path} with their source")
    with open(path) as f:
        row = json.load(f)
    if row["device_kind"] != device_kind:
        raise KeyError(f"{path} is for {row['device_kind']!r}")
    return row
