"""Loader for ``BENCHMARK.json`` and the files it names.

Everything a cell needs is found by name: ``configs/<config>.json``,
``traffic/<traffic>.json``, ``layer_metrics/<metric>.json`` (or ``.py``)
and, where a configuration names them, ``references/<module>.py`` and
``works/<module>.py``.
``load`` refuses a malformed benchmark before any run; later PRs add
files and entries and edit nothing here.
"""

from __future__ import annotations

import argparse
import ast
import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
# Keys of a configuration file that are not the model's config.json.
CONFIG_META_KEY = "bench"
# The rows a reference is run over where the configuration lists none:
# 4 prompts of 48 tokens continued by 16.
DEFAULT_REFERENCE_ROWS = [
    {"prompts": 4, "prompt_tokens": 48, "new_tokens": 16}]
REFERENCE_ENTRY = "greedy_continuations"
WORK_ENTRY = "layers"


class SpecError(ValueError):
    """The benchmark's files do not fit together; nothing may run."""


def _need(cond: bool, what: str) -> None:
    if not cond:
        raise SpecError(what)


def _read_json(path: str) -> dict:
    _need(os.path.isfile(path), f"missing file: {os.path.relpath(path, ROOT)}")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_peaks() -> dict:
    return _read_json(os.path.join(BENCH_DIR, "harness", "peaks.json"))


def peaks_for(device_kind: str) -> dict:
    """Published peaks of one chip; an unknown kind is an error."""
    table = load_peaks()["chips"]
    _need(device_kind in table,
          f"device_kind {device_kind!r} is not in harness/peaks.json")
    return table[device_kind]


def config_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "configs", f"{name}.json")


def traffic_path(name: str) -> str:
    return os.path.join(BENCH_DIR, "traffic", f"{name}.json")


def layer_metric_paths(name: str) -> tuple[str, str]:
    base = os.path.join(BENCH_DIR, "layer_metrics", name)
    return base + ".json", base + ".py"


def serve_sizes(serve_flags: list[str]) -> dict:
    """The four sizes that shape the compile lattice, and the page size,
    with ``serve``'s defaults (``cli.build_parser``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--max-batch-size", type=int, default=64)
    ap.add_argument("--max-num-tokens-per-batch", type=int, default=2048)
    ap.add_argument("--prefill-chunk-size", type=int, default=1024)
    ap.add_argument("--max-model-len", type=int, default=8192)
    ap.add_argument("--page-size", type=int, default=64)
    ns, _ = ap.parse_known_args(serve_flags)
    return vars(ns)


def reference_path(module: str | None) -> str:
    if module is None:
        return os.path.join(BENCH_DIR, "harness", "reference.py")
    return os.path.join(BENCH_DIR, "references", f"{module}.py")


def _defines(path: str, name: str) -> bool:
    """Whether the module at ``path`` binds ``name`` at its top level
    (a ``def``, an assignment or an import). Read, not imported: the
    parent never loads JAX."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), path)
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            bound = [node.name]
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            bound = [a.asname or a.name for a in node.names]
        elif isinstance(node, ast.Assign):
            bound = [t.id for t in node.targets if isinstance(t, ast.Name)]
        else:
            continue
        if name in bound:
            return True
    return False


def import_file(prefix: str, path: str):
    """The module at ``path``, loaded by its path (a reader, a work
    file: found by name in a directory that is no package)."""
    stem = os.path.splitext(os.path.basename(path))[0]
    found = importlib.util.spec_from_file_location(
        prefix + re.sub(r"\W", "_", stem), path)
    module = importlib.util.module_from_spec(found)
    found.loader.exec_module(module)
    return module


def reference_of(meta: dict, name: str = "") -> dict:
    """A configuration's plain reference, from its ``bench`` group:
    ``{"module", "import", "rows"}``. ``bench.reference`` is optional:
    ``{"module": "<stem>", "rows": [{"prompts", "prompt_tokens",
    "new_tokens"}, ...]}`` names ``references/<stem>.py``, which has
    ``greedy_continuations(params, cfg, prompts, new_tokens)`` as
    ``harness/reference.py`` has it; either key may be left out."""
    given = meta.get("reference") or {}
    _need(isinstance(given, dict) and set(given) <= {"module", "rows"},
          f"config {name}: bench.reference has the keys 'module' and 'rows'")
    module = given.get("module")
    if module is not None:
        _need(isinstance(module, str) and NAME_RE.match(module) is not None,
              f"config {name}: bench.reference.module {module!r} is no name")
    path = reference_path(module)
    _need(os.path.isfile(path),
          f"config {name}: missing file: {os.path.relpath(path, ROOT)}")
    _need(_defines(path, REFERENCE_ENTRY),
          f"config {name}: {os.path.relpath(path, ROOT)} has no "
          f"{REFERENCE_ENTRY}")
    rows = given.get("rows", DEFAULT_REFERENCE_ROWS)
    _need(isinstance(rows, list) and rows,
          f"config {name}: bench.reference.rows is a list of row shapes")
    longest = serve_sizes(list(meta.get("serve_flags", [])))["max_model_len"]
    for row in rows:
        _need(isinstance(row, dict)
              and set(row) == {"prompts", "prompt_tokens", "new_tokens"}
              and all(isinstance(v, int) and v > 0 for v in row.values()),
              f"config {name}: a reference row is {{prompts, prompt_tokens, "
              f"new_tokens}} in whole numbers above 0, not {row!r}")
        _need(row["prompt_tokens"] + row["new_tokens"] <= longest,
              f"config {name}: a reference row of {row['prompt_tokens']} + "
              f"{row['new_tokens']} tokens exceeds --max-model-len {longest}")
    return {"module": module or "harness/reference",
            "import": ("benchmarks.harness.reference" if module is None
                       else f"benchmarks.references.{module}"),
            "rows": rows}


def work_of(meta: dict, name: str = "") -> dict:
    """A configuration's work file, from its ``bench`` group:
    ``{"module", "path"}``. ``bench.work`` is optional: ``{"module":
    "<stem>"}`` names ``works/<stem>.py``, which describes the stage
    layer by layer for the shares of a roofline (``layers(cfg)``;
    ``harness/work.py`` ``stage`` has the contract). Without the key the
    stage is the dense block (``path`` None)."""
    given = meta.get("work")
    if given is None:
        return {"module": "harness/work", "path": None}
    _need(isinstance(given, dict) and set(given) == {"module"},
          f"config {name}: bench.work has the one key 'module'")
    module = given["module"]
    _need(isinstance(module, str) and NAME_RE.match(module) is not None,
          f"config {name}: bench.work.module {module!r} is no name")
    path = os.path.join(BENCH_DIR, "works", f"{module}.py")
    _need(os.path.isfile(path),
          f"config {name}: missing file: {os.path.relpath(path, ROOT)}")
    _need(_defines(path, WORK_ENTRY),
          f"config {name}: {os.path.relpath(path, ROOT)} has no {WORK_ENTRY}")
    return {"module": module, "path": path}


def load_config(name: str) -> dict:
    """``{"hf": config.json as run, "bench": the benchmark's notes}``."""
    raw = _read_json(config_path(name))
    meta = raw.get(CONFIG_META_KEY)
    _need(isinstance(meta, dict), f"config {name}: no '{CONFIG_META_KEY}' group")
    for key in ("source", "reduced", "assumed", "deployment", "chips",
                "serve_flags"):
        _need(key in meta, f"config {name}: bench.{key} missing")
    hf = {k: v for k, v in raw.items() if k != CONFIG_META_KEY}
    for key, change in meta["reduced"].items():
        _need(hf.get(key) == change["run"],
              f"config {name}: reduced key {key} says run={change['run']}, "
              f"the file holds {hf.get(key)}")
    return {"name": name, "hf": hf, "bench": meta,
            "reference": reference_of(meta, name),
            "work": work_of(meta, name)}


def load_traffic(name: str) -> dict:
    t = _read_json(traffic_path(name))
    _need(t.get("loop") in ("open", "closed"),
          f"traffic {name}: loop must be 'open' or 'closed'")
    if t["loop"] == "closed":
        _need(int(t.get("clients", 0)) > 0,
              f"traffic {name}: a closed loop needs 'clients'")
    else:
        arr = t.get("arrival") or {}
        _need(arr.get("process") in ("poisson", "gamma")
              and float(arr.get("rate_rps", 0)) > 0,
              f"traffic {name}: an open loop needs arrival.process and "
              "arrival.rate_rps")
    for key in ("prompt_tokens", "output_tokens", "sampling", "who", "why"):
        _need(key in t, f"traffic {name}: '{key}' missing")
    return dict(t, name=name)


def load_layer_metric(name: str) -> dict:
    """A metric's reader: its JSON description, or a ``.py`` with
    ``reduce(ctx)`` (then ``{"py": path}``)."""
    js, py = layer_metric_paths(name)
    if os.path.isfile(js):
        d = _read_json(js)
        _need(isinstance(d.get("source"), dict) and "kind" in d["source"],
              f"layer metric {name}: source.kind missing")
        return dict(d, name=name)
    _need(os.path.isfile(py),
          f"layer metric {name}: neither {os.path.relpath(js, ROOT)} nor .py")
    return {"name": name, "py": py}


def load(path: str | None = None) -> dict:
    """Read and check ``BENCHMARK.json`` and every file it names."""
    bench = _read_json(path or os.path.join(ROOT, "BENCHMARK.json"))
    for key in ("command", "paths", "run_seconds", "configs", "workloads",
                "end_to_end", "per_layer"):
        _need(key in bench, f"BENCHMARK.json: '{key}' missing")

    configs = {}
    for c in bench["configs"]:
        _need(NAME_RE.match(c["name"]) is not None,
              f"config name {c['name']!r} has a character outside the set")
        _need(c["name"] not in configs, f"config {c['name']} listed twice")
        _need(os.path.normpath(os.path.join(ROOT, c["file"]))
              == config_path(c["name"]),
              f"config {c['name']}: file must be "
              f"{os.path.relpath(config_path(c['name']), ROOT)}")
        loaded = load_config(c["name"])
        _need(sorted(c["reduced"]) == sorted(loaded["bench"]["reduced"]),
              f"config {c['name']}: 'reduced' differs between "
              "BENCHMARK.json and its file")
        _need(c["source"] == loaded["bench"]["source"],
              f"config {c['name']}: 'source' differs between "
              "BENCHMARK.json and its file")
        for key in c["reduced"]:
            _need(NAME_RE.match(key) is not None,
                  f"reduced key {key!r} has a character outside the set")
        configs[c["name"]] = loaded

    cells = {}
    for w in bench["workloads"]:
        for key in ("name", "config", "traffic"):
            _need(NAME_RE.match(w[key]) is not None,
                  f"workload {key} {w[key]!r} has a character outside the set")
        _need(w["name"] not in cells, f"workload {w['name']} listed twice")
        _need(w["config"] in configs,
              f"workload {w['name']}: config {w['config']} is not listed")
        _need(w["chips"] in (1, 4), f"workload {w['name']}: chips 1 or 4")
        _need(w["chips"] == configs[w["config"]]["bench"]["chips"],
              f"workload {w['name']}: chips differ from its config's")
        cells[w["name"]] = dict(w, traffic_spec=load_traffic(w["traffic"]))
    _need(len({(w["config"], w["traffic"]) for w in bench["workloads"]})
          == len(cells), "a pair of config and traffic appears twice")

    def cells_of(metric: dict) -> list[str]:
        listed = metric.get("workloads")
        if listed is None:
            return list(cells)
        for name in listed:
            _need(name in cells,
                  f"metric {metric['name']}: unknown workload {name}")
        return list(listed)

    seen = set()
    e2e = {}
    for m in bench["end_to_end"]:
        _need(NAME_RE.match(m["name"]) is not None
              and UNIT_RE.match(m["unit"]) is not None,
              f"metric {m['name']!r}/{m['unit']!r}: character outside the set")
        _need(m["name"] not in seen, f"metric {m['name']} listed twice")
        seen.add(m["name"])
        _need(m["better"] in ("lower", "higher"), f"{m['name']}: better")
        _need(m["source"] in ("host_clock", "device_trace"),
              f"{m['name']}: an end-to-end metric is host_clock or "
              "device_trace")
        _need(0 < float(m["bound"]) <= 0.1, f"{m['name']}: bound")
        e2e[m["name"]] = dict(m, cells=cells_of(m))
    _need("setup_s" in e2e and "workloads" not in e2e["setup_s"],
          "setup_s must be an end-to-end metric of every cell")

    per_layer = {}
    for m in bench["per_layer"]:
        _need(NAME_RE.match(m["name"]) is not None
              and UNIT_RE.match(m["unit"]) is not None,
              f"metric {m['name']!r}/{m['unit']!r}: character outside the set")
        _need(m["name"] not in seen, f"metric {m['name']} listed twice")
        seen.add(m["name"])
        _need(m["better"] in ("lower", "higher"), f"{m['name']}: better")
        _need(m["source"] in SOURCES, f"{m['name']}: source")
        _need(m["moves"] in e2e,
              f"{m['name']}: moves {m['moves']!r} is no end-to-end metric")
        for cell in cells_of(m):
            _need(cell in e2e[m["moves"]]["cells"],
                  f"{m['name']}: moves {m['moves']}, which cell {cell} "
                  "does not report")
        reader = load_layer_metric(m["name"])
        for key in ("layer", "unit", "moves"):
            if key in reader:
                _need(reader[key] == m[key],
                      f"{m['name']}: '{key}' differs between "
                      "BENCHMARK.json and its file")
        per_layer[m["name"]] = dict(m, cells=cells_of(m), reader=reader)

    for name in cells:
        _need(any(name in m["cells"] for k, m in e2e.items()
                  if k != "setup_s"),
              f"cell {name} reports no end-to-end metric besides setup_s")
        _need(any(name in m["cells"] for m in per_layer.values()),
              f"cell {name} reports no per-layer metric")
    load_peaks()
    return {"raw": bench, "configs": configs, "cells": cells,
            "end_to_end": e2e, "per_layer": per_layer}
