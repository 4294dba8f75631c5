"""A copy of the benchmark in a temporary tree, with tiny configurations
beside the real ones, for driving whole runs on the CPU.

Each tiny cell copies the limits of the real cell it stands for, so a
test that sees ``correct`` come out false sees it against the real limit.
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

#: tiny cell -> (config, traffic, the real cell whose limits it takes)
TINY_CELLS = {
    "gcn-tiny.full": ("gcn-tiny", "full", "gcn-reddit.full"),
    "gin-tiny.full": ("gin-tiny", "full", "gin-pubmed.full"),
    "gcn-tiny.serve": ("gcn-tiny", "poisson-tiny", "gcn-reddit.serve"),
}


#: the serving metrics, for a tree whose manifest has no serving cell
SERVE_END_TO_END = [
    {"name": "serve_p50_ms", "unit": "ms", "better": "lower", "bound": 0.25,
     "source": "host_clock"},
    {"name": "serve_p95_ms", "unit": "ms", "better": "lower", "bound": 0.25,
     "source": "host_clock"}]
SERVE_PER_LAYER = [
    {"name": "serve.admit_ms", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "serve admission",
     "moves": "serve_p50_ms"},
    {"name": "loadgen.late_p95_ms", "unit": "ms", "better": "lower",
     "source": "host_clock", "layer": "load generator",
     "moves": "serve_p95_ms"}]


def _add_missing(bench, section, metrics, cell):
    names = {m["name"] for m in bench[section]}
    for m in metrics:
        if m["name"] not in names:
            bench[section].append(dict(m, workloads=[cell]))


def _tiny_config(real: str, name: str, v: int, e: int, f: int, c: int):
    cfg = json.loads((REPO / "chipbench" / "configs" /
                      f"{real}.json").read_text())
    cfg["name"] = name
    cfg["graph"].update(num_vertices=v, num_edges=e, feature_len=f,
                        num_classes=c)
    if "serve" in cfg:
        cfg["serve"].update(fanouts=[3, 2], seed_levels=[2, 4])
    return cfg


def make_tree(dst: Path) -> Path:
    """``dst`` holding ``BENCHMARK.json`` and ``chipbench/`` with the tiny
    cells added as files and manifest entries only; returns ``dst``."""
    shutil.copy(REPO / "BENCHMARK.json", dst / "BENCHMARK.json")
    shutil.copytree(REPO / "chipbench", dst / "chipbench",
                    ignore=shutil.ignore_patterns(".*", "__pycache__"))
    pkg = dst / "chipbench"
    configs = {"gcn-tiny": _tiny_config("gcn-reddit", "gcn-tiny", 300,
                                        2400, 48, 5),
               "gin-tiny": _tiny_config("gin-pubmed", "gin-tiny", 200, 600,
                                        40, 3)}
    for name, cfg in configs.items():
        (pkg / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    (pkg / "traffic" / "poisson-tiny.json").write_text(json.dumps({
        "kind": "open_loop", "rate_rps": 40, "seeds_per_request": [1, 4],
        "mix_seed": 0, "drain_s": 30, "check_requests": 8}))
    bench = json.loads((dst / "BENCHMARK.json").read_text())
    for name in configs:
        bench["configs"].append({
            "name": name, "source": "https://arxiv.org/abs/2001.10160",
            "file": f"chipbench/configs/{name}.json",
            "reduced": ["graph"], "why": "a tiny stand-in for CPU tests"})
    for cell, (config, traffic, real) in TINY_CELLS.items():
        shutil.copy(pkg / "limits" / f"{real}.json",
                    pkg / "limits" / f"{cell}.json")
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": traffic, "chips": 1,
                                   "why": "a tiny stand-in for CPU tests"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if real in m.get("workloads", []):
                m["workloads"].append(cell)
    _add_missing(bench, "end_to_end", SERVE_END_TO_END, "gcn-tiny.serve")
    _add_missing(bench, "per_layer", SERVE_PER_LAYER, "gcn-tiny.serve")
    (dst / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return dst
