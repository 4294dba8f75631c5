"""The readers of the program's own spans and counters
(``forward.call_ms``, ``agg.slot_ratio``, ``agg.gather_mb``) on whole tiny
runs on the CPU: they read the window's ``plan.call`` spans and the
layout gauges the program publishes, and read nothing where the program
has nothing to give."""

import json

import pytest

from chipbench.manifest import Manifest, load_module
from chipbench.metrics_api import Readings
from chipbench.run import run_cell
from chipbench.tests.tiny import make_tree

SEED = 2**31 + 11
READERS = ("forward.call_ms", "agg.slot_ratio", "agg.gather_mb")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny tree, with the GIN cell on the Pallas kernels (interpreted
    on the CPU), as a TPU runs it; the GCN cell stays on XLA."""
    root = make_tree(tmp_path_factory.mktemp("bench"))
    path = root / "chipbench" / "configs" / "gin-tiny.json"
    cfg = json.loads(path.read_text())
    cfg["plan"]["backend"] = "pallas-tpu"
    path.write_text(json.dumps(cfg))
    return root


def _read(root, cell, seconds=0.3):
    from repro.core.plan import build_plan
    from repro.profile import spans
    spans.reset()
    _, outcome, ctx = run_cell(root, cell, SEED, seconds, False)
    manifest = Manifest.load(root)
    r = Readings(ctx=ctx, outcome=outcome, peaks={})
    got = {n: load_module(manifest.metric_file(n), n).read(r)
           for n in READERS}
    return got, outcome, ctx, manifest, build_plan


def test_readers_listed_for_the_tiny_cells(root):
    manifest = Manifest.load(root)
    for cell in ("gin-tiny.full", "gcn-tiny.full"):
        assert set(READERS) <= set(manifest.cell(cell)["per_layer"])


def test_readers_read_the_window_and_the_layout(root):
    got, outcome, ctx, _, build_plan = _read(root, "gin-tiny.full")
    assert 0 < got["forward.call_ms"] < 1e3 * ctx.seconds
    from repro.profile import spans
    window = spans.spans("plan.call", since=ctx.setup_end)
    # the window's calls, not set-up's two warm-up calls
    assert len(window) == outcome.attempted
    assert got["forward.call_ms"] == pytest.approx(
        1e3 * sum(s.seconds for s in window) / len(window))

    # the counts describe() gives for the same plan
    from chipbench.harness import model_config, program_graph
    cfg = json.loads((root / "chipbench" / "configs" /
                      "gin-tiny.json").read_text())
    gs = cfg["graph"]
    g, _, _ = program_graph(cfg)
    plan = build_plan(g, model_config(cfg), gs["feature_len"],
                      gs["num_classes"], backend="pallas-tpu")
    desc = plan.describe()
    slots = sum(d["agg_kernel_slots"] for d in desc)
    edges = sum(d["agg_edges"] for d in desc)
    assert edges == 2 * gs["num_edges"]
    assert got["agg.slot_ratio"] == pytest.approx(slots / edges)
    assert got["agg.slot_ratio"] > 1.0
    assert got["agg.gather_mb"] == pytest.approx(
        sum(d["agg_gather_bytes"] for d in desc) / 1e6)


def test_layout_readers_read_nothing_without_a_blocked_layout(root):
    got, _, _, _, _ = _read(root, "gcn-tiny.full", seconds=0.2)
    assert got["forward.call_ms"] > 0
    assert got["agg.slot_ratio"] is None and got["agg.gather_mb"] is None


def test_readers_read_nothing_from_a_program_without_a_registry(
        root, monkeypatch):
    import sys
    got, outcome, ctx, manifest, _ = _read(root, "gin-tiny.full", 0.2)
    monkeypatch.setitem(sys.modules, "repro.profile.spans", None)
    import repro.profile
    monkeypatch.delattr(repro.profile, "spans", raising=False)
    r = Readings(ctx=ctx, outcome=outcome, peaks={})
    for n in READERS:
        assert load_module(manifest.metric_file(n), n).read(r) is None
