"""Find everything of one cell by the names in `BENCHMARK.json`.

A cell names a configuration and a traffic mix; a metric names a reader.
Each sits in a file of its own, found by that name, so a later cell,
configuration or metric is added as files and entries, never by editing
one that is there:

* configuration: the entry's ``file`` (JSON sizes), whose ``builder``
  names `bench/models/<builder>.py` and its plain reference
  `bench/models/<builder>_ref.py`;
* traffic mix: `bench/traffic/<traffic>.json`;
* metric: `bench/metrics/<name>.py`, whose ``read(ctx)`` returns a number
  or None (nothing to read: the metric is left out of the line). A
  quantity split by the end-to-end metric it moves (``idle_frac.closed``
  beside ``idle_frac``) reads with the reader of its base name, unless it
  has a file of its own;
* a kernel's or a stage's work count: `bench/counts/<name>.py`;
* the chip's peaks: `bench/peaks.json`, keyed by `device_kind`.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[1]


class BenchError(RuntimeError):
    """The run cannot give a result: it exits non-zero and prints none."""


def read_json(path: pathlib.Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError as e:
        raise BenchError(f"missing file {path}") from e


def load_spec(root: pathlib.Path = ROOT) -> dict:
    return read_json(root / "BENCHMARK.json")


def cell(spec: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    """The workload entry `name` with its configuration entry and the
    contents of its configuration and traffic files."""
    work = {w["name"]: w for w in spec["workloads"]}
    if name not in work:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json "
                         f"(have {sorted(work)})")
    w = work[name]
    configs = {c["name"]: c for c in spec["configs"]}
    entry = configs[w["config"]]
    return {"workload": w, "config_entry": entry,
            "config": read_json(root / entry["file"]),
            "traffic": read_json(root / "bench" / "traffic"
                                 / f"{w['traffic']}.json")}


def load_file(path: pathlib.Path, name: str):
    """Import the Python file `path` as a module called `name`."""
    if not path.is_file():
        raise BenchError(f"missing file {path}")
    module_spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module


def builder(config: dict):
    """(builder module, reference module) of a configuration."""
    name = config["builder"]
    return (importlib.import_module(f"bench.models.{name}"),
            importlib.import_module(f"bench.models.{name}_ref"))


def metrics(spec: dict, workload: str, kind: str,
            root: pathlib.Path = ROOT) -> list:
    """[(metric entry, reader module)] of the `kind` ("end_to_end" or
    "per_layer") metrics that apply to `workload`."""
    out = []
    for m in spec[kind]:
        if workload in m.get("workloads", [workload]):
            out.append((m, reader(m["name"], root)))
    return out


def reader(name: str, root: pathlib.Path = ROOT):
    """The reader module of metric `name`: `bench/metrics/<name>.py`, else
    that of the name with its last ``.<part>`` taken off, and so on."""
    base = name
    while not (root / "bench" / "metrics" / f"{base}.py").is_file():
        if "." not in base:
            base = name  # the error names the metric's own file
            break
        base = base.rsplit(".", 1)[0]
    return load_file(root / "bench" / "metrics" / f"{base}.py",
                     f"bench_metric_{base}")


def count(name: str, root: pathlib.Path = ROOT):
    """The work-count module `bench/counts/<name>.py`."""
    return load_file(root / "bench" / "counts" / f"{name}.py",
                     f"bench_count_{name}")


def peaks(device_kind: str, root: pathlib.Path = ROOT) -> dict:
    """The published peaks of `device_kind`; an unknown kind is an error."""
    table = read_json(root / "bench" / "peaks.json")
    if device_kind not in table["devices"]:
        raise BenchError(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json (have {sorted(table['devices'])})")
    return table["devices"][device_kind]
