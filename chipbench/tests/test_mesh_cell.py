"""The four-chip kind, ``mesh_forward``, run whole on the CPU at a tiny
size, in a child process with four virtual devices (this process keeps
its one): a sound run is correct, the bfloat16 control and an altered
answer are not, and the program's halo gauges reach the readers."""

import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

from chipbench.tests.tiny import REPO, make_tree

SEED = 2**31 + 13
CELL = "gin-tiny.ring4"
V, E, F, C = 203, 900, 40, 3       # 203 rows over 4 blocks of 51: ragged


def _add_tiny_mesh_cell(root):
    """A tiny stand-in for ``gin-reddit.ring4`` in the tree, with its
    limits, listed by every metric that lists the real cell."""
    pkg = root / "chipbench"
    cfg = json.loads((pkg / "configs" / "gin-reddit.json").read_text())
    cfg["name"] = "gin-tiny-mesh"
    cfg["graph"].update(num_vertices=V, num_edges=E, feature_len=F,
                        num_classes=C)
    (pkg / "configs" / "gin-tiny-mesh.json").write_text(json.dumps(cfg))
    shutil.copy(pkg / "limits" / "gin-reddit.ring4.json",
                pkg / "limits" / f"{CELL}.json")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "gin-tiny-mesh", "source": "https://arxiv.org/abs/2001.10160",
        "file": "chipbench/configs/gin-tiny-mesh.json", "reduced": ["graph"],
        "why": "a tiny stand-in for CPU tests"})
    bench["workloads"].append({"name": CELL, "config": "gin-tiny-mesh",
                               "traffic": "ring4", "chips": 4,
                               "why": "a tiny stand-in for CPU tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "gin-reddit.ring4" in m.get("workloads", []):
            m["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return cfg


CHILD = """
import json, os, sys
from pathlib import Path
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
root = Path(sys.argv[1])
sys.path[:0] = [str(root), os.path.join({repo!r}, "src")]
import jax.numpy as jnp
from chipbench.manifest import Manifest, load_module
from chipbench.metrics_api import Readings
from chipbench.run import run_cell
from repro.core.plan import CompiledPlan
from repro.profile import spans

spans.reset()
result, outcome, ctx = run_cell(root, {cell!r}, {seed}, 0.3, False)
manifest = Manifest.load(root)
r = Readings(ctx=ctx, outcome=outcome, peaks={{}})
# the gauges of the last plan traced: read before the control's plan
readers = {{n: load_module(manifest.metric_file(n), n).read(r)
           for n in ("halo.wire_mb", "agg.slot_ratio", "agg.gather_mb")}}
print(json.dumps({{"sound": result, "counters": outcome.counters,
                  "compiles": ctx.compiles, "readers": readers}}))
_, outcome, _ = run_cell(root, {cell!r}, {seed} + 2, 0.1, False,
                         control=True)
print(json.dumps({{"control": outcome.control}}))

call = CompiledPlan.__call__
def broken(self, *a, **kw):
    out = jnp.asarray(call(self, *a, **kw))
    return out.at[out.shape[0] // 3, 0].add(0.05 * (abs(out).max() + 1e-3))
CompiledPlan.__call__ = broken
result, _, _ = run_cell(root, {cell!r}, {seed} + 1, 0.3, False)
print(json.dumps({{"altered": result}}))
"""


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    root = make_tree(tmp_path_factory.mktemp("bench"))
    cfg = _add_tiny_mesh_cell(root)
    env = {"PATH": os.environ.get("PATH", ""),
           "HOME": os.environ.get("HOME", ""), "JAX_PLATFORMS": "cpu"}
    child = textwrap.dedent(CHILD).format(repo=str(REPO), cell=CELL,
                                          seed=SEED)
    p = subprocess.run([sys.executable, "-c", child, str(root)], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    out = {}
    for line in p.stdout.splitlines():
        if line.startswith("{"):
            out.update(json.loads(line))
    return out, cfg


def test_sound_run_is_correct_and_control_is_not(runs):
    out, _ = runs
    sound = out["sound"]
    assert sound["correct"], sound["checks"]
    assert out["compiles"] == 0
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert sound["device"]["count"] == 4
    assert list(sound["metrics"]) == ["setup_s", "forward_ms"]
    limit = sound["checks"]["logit_err"]["limit"]
    assert out["control"]["logit_err"] > limit
    c = out["counters"]
    assert c["traces"] == c["traces_after_window"] == 1
    assert (c["partition"], c["strategy"], c["overlap"]) == \
        ("1d", "ring", "pipelined")
    assert c["order"] == ["aggregate_first"] * 2


def test_altered_answer_is_not_correct(runs):
    out, _ = runs
    assert not out["altered"]["correct"], out["altered"]["checks"]


def test_halo_readers_read_the_programs_gauges(runs):
    out, cfg = runs
    block = -(-V // 4)
    widths = F + cfg["model"]["hidden_dims"][0]
    # pipelined: three slabs a layer leave each device, at 4 bytes
    assert out["readers"]["halo.wire_mb"] == pytest.approx(
        3 * block * widths * 4 / 1e6)
    assert out["readers"]["agg.slot_ratio"] >= 1.0
    assert out["readers"]["agg.gather_mb"] > 0
