"""``BENCHMARK.json``: the rules it is held to, and that a configuration, a
cell and a metric are added as new files and entries alone."""

import copy
import json
from pathlib import Path

import pytest

from chipbench.manifest import Manifest, ManifestError, load_module
from chipbench.tests.tiny import REPO, make_tree


@pytest.fixture(scope="module")
def real():
    return Manifest.load(REPO)


def _broken(tmp_path, edit):
    """A copy of the real tree with ``edit(data)`` applied to its
    ``BENCHMARK.json``; loading it must raise."""
    make_tree(tmp_path)
    data = json.loads((REPO / "BENCHMARK.json").read_text())
    edit(data)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(data))
    with pytest.raises(ManifestError):
        Manifest.load(tmp_path)


def test_real_manifest_resolves_every_name(real):
    for cell in real.workloads:
        joined = real.cell(cell)
        assert joined["config_data"]["name"] == joined["config"]
        assert joined["traffic_data"]["kind"]
        for metric in joined["per_layer"]:
            assert callable(load_module(real.metric_file(metric),
                                        metric).read)
        assert joined["end_to_end"][0] == "setup_s"


def test_every_moves_is_reported_by_the_metrics_cells(real):
    for name, m in real.per_layer.items():
        for cell in m.get("workloads", real.workloads):
            assert m["moves"] in real.cell_end_to_end(cell), (name, cell)


@pytest.mark.parametrize("edit", [
    lambda d: d["end_to_end"][1].update(name="forward ms"),
    lambda d: d["end_to_end"][1].update(name="a/b"),
    lambda d: d["per_layer"][0].update(unit="per cent"),
    lambda d: d["per_layer"][0].update(unit="µs"),
    lambda d: d["per_layer"][0].update(moves="setup_s"),
    lambda d: d["per_layer"][0].update(moves="serve_p50_ms"),
    lambda d: d["per_layer"][0].update(why="extra key"),
    lambda d: d["end_to_end"][1].update(bound=0.3),
    lambda d: d["end_to_end"].pop(0),
    lambda d: d["workloads"][0].update(traffic="no-such-traffic"),
    lambda d: d["workloads"][0].update(chips=2),
    lambda d: d["workloads"].append(copy.deepcopy(d["workloads"][0])),
    lambda d: d["configs"][0].update(file="chipbench/configs/none.json"),
    lambda d: d.update(command=["python3", "/abs/run.py"]),
    lambda d: d.update(run_seconds=52),
], ids=["space-in-name", "slash-in-name", "space-in-unit",
        "non-ascii-unit", "moves-setup", "moves-unreported", "extra-key",
        "bound-too-loose", "no-setup", "unknown-traffic", "two-chips",
        "duplicate-cell", "missing-config-file", "absolute-command",
        "run-seconds"])
def test_a_broken_manifest_is_refused(tmp_path, edit):
    _broken(tmp_path, edit)


def test_a_metric_file_that_is_missing_is_refused(tmp_path):
    make_tree(tmp_path)
    (tmp_path / "chipbench" / "metrics" / "forward.mfu.py").unlink()
    with pytest.raises(ManifestError):
        Manifest.load(tmp_path)


def test_added_files_are_found_without_editing_any_file(tmp_path):
    """A configuration, a cell and a per-layer metric added as new files
    plus manifest entries: every existing file stays byte for byte."""
    before = {p.relative_to(REPO / "chipbench"): p.read_bytes()
              for p in (REPO / "chipbench").rglob("*")
              if p.is_file() and "__pycache__" not in p.parts
              and not any(part.startswith(".") for part in p.parts)}
    root = make_tree(tmp_path)
    pkg = root / "chipbench"
    (pkg / "metrics" / "tiny.flag.py").write_text(
        "def read(r):\n    return 42.0\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "tiny.flag", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "model step",
        "moves": "forward_ms", "workloads": ["gin-tiny.full"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    m = Manifest.load(root)
    assert "gcn-tiny" in m.configs and "gin-tiny.full" in m.workloads
    assert "tiny.flag" in m.cell_per_layer("gin-tiny.full")
    assert "tiny.flag" not in m.cell_per_layer("gin-pubmed.full")
    cell = m.cell("gcn-tiny.serve")
    assert cell["config_data"]["graph"]["num_vertices"] == 300
    assert cell["traffic_data"]["kind"] == "open_loop"
    assert load_module(m.metric_file("tiny.flag"), "tiny.flag").read(
        None) == 42.0
    for rel, data in before.items():
        assert (pkg / rel).read_bytes() == data, rel
