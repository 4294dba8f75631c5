"""``BENCHMARK.json``: load, validate, and resolve every name to its file.

Nothing here imports JAX or the program: a manifest can be checked on any
machine.  ``Manifest.load`` raises ``ManifestError`` on the first rule it
finds broken.
"""

from __future__ import annotations

import importlib.util
import json
import re
from pathlib import Path
from typing import Dict, List

PKG = "chipbench"
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH_RE = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
E2E_SOURCES = {"host_clock", "device_trace"}
SOURCES = E2E_SOURCES | {"program_span", "program_counter"}
SETUP = "setup_s"
MAX_BOUND = 0.25


class ManifestError(ValueError):
    """``BENCHMARK.json`` or a file it names breaks a rule."""


def _need(cond: bool, msg: str) -> None:
    if not cond:
        raise ManifestError(msg)


def _line(text, what: str) -> None:
    _need(isinstance(text, str) and 1 <= len(text) <= 200
          and "\n" not in text and "\t" not in text,
          f"{what}: 1 to 200 characters on one line, no tab")


def _name(name, what: str) -> None:
    _need(isinstance(name, str) and bool(NAME_RE.match(name)),
          f"{what}: {name!r} is not a valid name")


def _keys(entry: dict, required: set, what: str,
          optional: set = frozenset()) -> None:
    _need(isinstance(entry, dict), f"{what}: not an object")
    keys = set(entry)
    _need(required <= keys <= required | optional,
          f"{what}: keys {sorted(keys)}, want {sorted(required)}"
          + (f" (+ {sorted(optional)})" if optional else ""))


def _unique(entries: List[dict], what: str) -> Dict[str, dict]:
    out: Dict[str, dict] = {}
    for e in entries:
        _need(e["name"] not in out, f"{what}: {e['name']!r} appears twice")
        out[e["name"]] = e
    return out


class Manifest:
    """A validated ``BENCHMARK.json`` rooted at a checkout."""

    def __init__(self, root: Path, data: dict):
        self.root = Path(root)
        self.data = data
        self.configs = _unique(data["configs"], "configs")
        self.workloads = _unique(data["workloads"], "workloads")
        self.end_to_end = _unique(data["end_to_end"], "end_to_end")
        self.per_layer = _unique(data["per_layer"], "per_layer")
        _need(not set(self.end_to_end) & set(self.per_layer),
              "a metric name is both end-to-end and per-layer")

    @classmethod
    def load(cls, root) -> "Manifest":
        root = Path(root)
        path = root / "BENCHMARK.json"
        _need(path.is_file(), f"no BENCHMARK.json in {root}")
        _need(path.stat().st_size <= 64 * 1024, "BENCHMARK.json over 64 KiB")
        data = json.loads(path.read_text())
        _validate_shape(data)
        m = cls(root, data)
        m._validate_links()
        return m

    # -- what a cell reports -------------------------------------------------

    def cell_end_to_end(self, cell: str) -> List[str]:
        """End-to-end metrics the cell reports, ``setup_s`` first."""
        return [n for n, e in self.end_to_end.items()
                if cell in e.get("workloads", [cell])]

    def cell_per_layer(self, cell: str) -> List[str]:
        """Per-layer metrics read in the cell's traced run."""
        e2e = set(self.cell_end_to_end(cell))
        return [n for n, e in self.per_layer.items()
                if (cell in e["workloads"] if "workloads" in e
                    else e["moves"] in e2e)]

    # -- name -> file --------------------------------------------------------

    def config_file(self, config: str) -> Path:
        return self.root / self.configs[config]["file"]

    def traffic_file(self, traffic: str) -> Path:
        return self.root / PKG / "traffic" / f"{traffic}.json"

    def kind_file(self, kind: str) -> Path:
        return self.root / PKG / "kinds" / f"{kind}.py"

    def metric_file(self, metric: str) -> Path:
        return self.root / PKG / "metrics" / f"{metric}.py"

    def limits_file(self, cell: str) -> Path:
        return self.root / PKG / "limits" / f"{cell}.json"

    def cell(self, name: str) -> dict:
        """The cell's manifest entry joined with its config, traffic and
        limits files (``ManifestError`` for an unknown cell)."""
        _need(name in self.workloads, f"no workload named {name!r}")
        w = self.workloads[name]
        traffic = json.loads(self.traffic_file(w["traffic"]).read_text())
        return {**w,
                "config_data": json.loads(
                    self.config_file(w["config"]).read_text()),
                "traffic_data": traffic,
                "limits": json.loads(self.limits_file(name).read_text()),
                "end_to_end": self.cell_end_to_end(name),
                "per_layer": self.cell_per_layer(name)}

    def _validate_links(self) -> None:
        used = {w["config"] for w in self.workloads.values()}
        _need(set(self.configs) == used,
              f"configs without a cell: {sorted(set(self.configs) - used)}; "
              f"cells of unknown configs: {sorted(used - set(self.configs))}")
        files = [c["file"] for c in self.configs.values()]
        _need(len(set(files)) == len(files), "two configs share a file")
        paths = self.data["paths"]
        for c in self.configs.values():
            _need(any(c["file"].startswith(p.rstrip("/") + "/")
                      for p in paths),
                  f"config {c['name']}: file {c['file']} is not under paths")
            _need(self.config_file(c["name"]).is_file(),
                  f"config {c['name']}: no file {c['file']}")
        pairs = set()
        for w in self.workloads.values():
            pair = (w["config"], w["traffic"])
            _need(pair not in pairs, f"cell {w['name']}: {pair} twice")
            pairs.add(pair)
            tf = self.traffic_file(w["traffic"])
            _need(tf.is_file(), f"cell {w['name']}: no traffic file {tf}")
            kind = json.loads(tf.read_text()).get("kind")
            _need(isinstance(kind, str) and self.kind_file(kind).is_file(),
                  f"traffic {w['traffic']}: no generator for kind {kind!r}")
            _need(self.limits_file(w["name"]).is_file(),
                  f"cell {w['name']}: no limits file")
        four = sum(w["chips"] == 4 for w in self.workloads.values())
        _need(four <= max(1, len(self.workloads) // 2),
              "too many four-chip cells")
        for n, e in self.end_to_end.items():
            for c in e.get("workloads", []):
                _need(c in self.workloads, f"metric {n}: unknown cell {c}")
        for n, e in self.per_layer.items():
            _need(e["moves"] in self.end_to_end and e["moves"] != SETUP,
                  f"metric {n}: moves {e['moves']!r}, not an end-to-end "
                  "metric other than setup_s")
            cells = e.get("workloads", [
                c for c in self.workloads
                if e["moves"] in self.cell_end_to_end(c)])
            for c in cells:
                _need(c in self.workloads, f"metric {n}: unknown cell {c}")
                _need(e["moves"] in self.cell_end_to_end(c),
                      f"metric {n}: cell {c} does not report {e['moves']}")
            _need(self.metric_file(n).is_file(), f"metric {n}: no reader")
        layers: Dict[str, str] = {}
        for w in self.workloads:
            e2e = self.cell_end_to_end(w)
            _need(SETUP in e2e and len(e2e) >= 2,
                  f"cell {w}: needs setup_s and one more end-to-end metric")
            _need(self.cell_per_layer(w), f"cell {w}: no per-layer metric")
        for n, e in self.per_layer.items():
            layers.setdefault(e["layer"].lower(), e["layer"])
            _need(layers[e["layer"].lower()] == e["layer"],
                  f"metric {n}: layer {e['layer']!r} spelled two ways")


def _validate_shape(d: dict) -> None:
    """Every rule that needs no file but ``BENCHMARK.json`` itself."""
    _need(isinstance(d, dict) and set(d) == TOP_KEYS,
          f"top-level keys must be exactly {sorted(TOP_KEYS)}")
    cmd = d["command"]
    _need(isinstance(cmd, list) and 1 <= len(cmd) <= 32,
          "command: a list of 1 to 32 strings")
    for word in cmd:
        _line(word, "command word")
        _need(not word.startswith("/") and ".." not in word.split("/"),
              f"command word {word!r} leaves the checkout")
    paths = d["paths"]
    _need(isinstance(paths, list) and 1 <= len(paths) <= 16, "paths: 1-16")
    for p in paths:
        _need(isinstance(p, str) and bool(PATH_RE.match(p))
              and not p.startswith("/") and ".." not in p.split("/"),
              f"paths: {p!r}")
    rs = d["run_seconds"]
    _need(isinstance(rs, int) and not isinstance(rs, bool) and 1 <= rs <= 51,
          "run_seconds: a whole number from 1 to 51")
    sections = (("configs", CONFIG_KEYS, set(), 24),
                ("workloads", WORKLOAD_KEYS, set(), 24),
                ("end_to_end", E2E_KEYS, {"workloads"}, 16),
                ("per_layer", LAYER_KEYS, {"workloads"}, 128))
    for sec, req, opt, most in sections:
        entries = d[sec]
        _need(isinstance(entries, list) and 1 <= len(entries) <= most,
              f"{sec}: 1 to {most} entries")
        for e in entries:
            _keys(e, req, f"{sec} entry", opt)
            _name(e["name"], f"{sec} name")
    for c in d["configs"]:
        _line(c["source"], f"config {c['name']} source")
        _line(c["why"], f"config {c['name']} why")
        _need(isinstance(c["reduced"], list) and len(c["reduced"]) <= 16,
              f"config {c['name']}: reduced is a list of at most 16 keys")
        for k in c["reduced"]:
            _name(k, f"config {c['name']} reduced key")
    for w in d["workloads"]:
        _name(w["config"], f"cell {w['name']} config")
        _name(w["traffic"], f"cell {w['name']} traffic")
        _need(w["chips"] in (1, 4), f"cell {w['name']}: chips is 1 or 4")
        _line(w["why"], f"cell {w['name']} why")
    for sec in ("end_to_end", "per_layer"):
        for m in d[sec]:
            _need(isinstance(m["unit"], str) and bool(UNIT_RE.match(m["unit"])),
                  f"metric {m['name']}: unit {m['unit']!r}")
            _need(m["better"] in ("lower", "higher"),
                  f"metric {m['name']}: better is lower or higher")
            _need(m["source"] in (E2E_SOURCES if sec == "end_to_end"
                                  else SOURCES),
                  f"metric {m['name']}: source {m['source']!r}")
            if "workloads" in m:
                _need(isinstance(m["workloads"], list) and m["workloads"],
                      f"metric {m['name']}: workloads is a non-empty list")
                for c in m["workloads"]:
                    _name(c, f"metric {m['name']} cell")
    for m in d["end_to_end"]:
        b = m["bound"]
        _need(isinstance(b, (int, float)) and 0.01 <= b <= MAX_BOUND,
              f"metric {m['name']}: bound {b!r} outside [0.01, 0.25]")
    _need(SETUP in {m["name"] for m in d["end_to_end"]},
          "end_to_end: setup_s is missing")
    for m in d["per_layer"]:
        _line(m["layer"], f"metric {m['name']} layer")


def load_module(path: Path, name: str):
    """Import one file (a traffic kind or a metric reader) by its path."""
    spec = importlib.util.spec_from_file_location(
        f"{PKG}._loaded.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
