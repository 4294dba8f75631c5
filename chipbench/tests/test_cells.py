"""Whole runs on the CPU at tiny sizes: a run without a TPU fails; a sound
run is correct; the control, and each fault the cells can have, is not."""

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.run import run_cell
from chipbench.tests.tiny import REPO, TINY_CELLS, make_tree

SEED = 2**31 + 7   # the driver's seeds pass 32 bits


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_tree(tmp_path_factory.mktemp("bench"))


def test_no_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "gin-pubmed.full",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "metrics" not in p.stdout and "{" not in p.stdout
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("cell", sorted(TINY_CELLS))
def test_sound_run_is_correct_and_control_is_not(root, cell):
    result, outcome, ctx = run_cell(root, cell, SEED, 0.3, False,
                                    control=True)
    assert ctx.compiles == 0
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    metrics = result["metrics"]
    assert list(metrics)[0] == "setup_s"
    assert all(m["value"] > 0 for m in metrics.values())
    assert list(result)[-1] == "checks"
    limit = result["checks"]["logit_err"]["limit"]
    assert outcome.control["logit_err"] > limit


def _patch_output(monkeypatch, cell, alter):
    """Break the timed path underneath, where the program produces its
    answer: every compiled forward's logits (full-graph cells) or every
    request's seed logits (serving) go through ``alter``."""
    from repro.core.plan import CompiledPlan
    from repro.serve.graph_engine import GraphServeEngine
    cls, name = (GraphServeEngine, "run_prepared") if "serve" in cell \
        else (CompiledPlan, "__call__")
    call = getattr(cls, name)

    def broken(self, *a, **kw):
        return alter(jnp.asarray(call(self, *a, **kw)))
    monkeypatch.setattr(cls, name, broken)


def _one_logit_off(out):
    return out.at[out.shape[0] // 3, 0].add(0.05 * (abs(out).max() + 1e-3))


def _half_left_out(out):
    # the rows of the second half of the batch are never produced
    return out.at[out.shape[0] // 2:].set(0.0)


@pytest.mark.parametrize("cell", ["gcn-tiny.full", "gcn-tiny.serve"])
@pytest.mark.parametrize("fault", [_one_logit_off, _half_left_out],
                         ids=["answer-altered", "half-left-out"])
def test_fault_makes_run_incorrect(root, monkeypatch, cell, fault):
    _patch_output(monkeypatch, cell, fault)
    result, _, _ = run_cell(root, cell, SEED + 1, 0.3, False)
    assert not result["correct"], result["checks"]


def test_request_left_unanswered_is_failed(root, monkeypatch):
    from repro.serve.graph_engine import GraphServeEngine
    step = GraphServeEngine._step

    def drop_one(self):
        done = step(self)
        return [r for r in done if r.rid != 3]
    monkeypatch.setattr(GraphServeEngine, "_step", drop_one)
    result, _, _ = run_cell(root, "gcn-tiny.serve", SEED + 2, 0.3, False)
    assert not result["correct"]
    assert result["checks"]["unanswered"]["value"] >= 1


def test_result_line_is_json_with_the_contract_keys(root):
    result, _, _ = run_cell(root, "gin-tiny.full", SEED + 3, 0.2, False)
    line = json.loads(json.dumps(result))
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(line["device"])
    assert np.isfinite(line["metrics"]["forward_ms"]["value"])
