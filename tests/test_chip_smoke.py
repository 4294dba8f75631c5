"""chip_smoke.py off the chip: it refuses to report without a TPU, and its
phases (the same code the chip runs) pass at a tiny Pubmed-shaped size on
CPU -- so a change that breaks the smoke fails here before any chip time."""

import importlib.util
import json
from pathlib import Path

import pytest

from repro.config import PUBMED, reduced_graph
from test_distributed import run_sub

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(smoke):
    spec = reduced_graph(PUBMED, 300, 48)
    g, x = smoke._graph(spec)
    return spec, g, x


def test_refuses_without_tpu(smoke, capsys):
    with pytest.raises(SystemExit, match="no TPU found"):
        smoke.main([])
    assert "ok" not in capsys.readouterr().out


def test_refuses_forced_interpret_mode(smoke, monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    with pytest.raises(SystemExit, match="REPRO_PALLAS_INTERPRET"):
        smoke.main([])


def test_forward_phases_tiny(smoke, tiny):
    records = smoke.phase_gcn(*tiny) + smoke.phase_gin(*tiny)
    assert [r["phase"] for r in records] == \
        ["gcn/auto", "gcn/auto/fused", "gcn/xla", "gin/auto"]
    assert all(r["num_traces"] == 1 and r["band_use"] <= 1
               for r in records)
    json.dumps(records)                      # every record prints


def test_serve_phase_tiny(smoke, tiny):
    rec, = smoke.phase_serve(*tiny, num_requests=4)
    assert rec["requests"] == 4
    assert rec["bucket_misses"] == 0 and rec["retraces"] == 0


def test_compile_cache_location():
    """The entry points' cache: $JAX_COMPILATION_CACHE_DIR when set (and no
    directory set in code), else the checkout's fixed .jax_cache/."""
    out = run_sub("""
        import os
        from repro.launch.cache import CHECKOUT_CACHE_DIR, enable_compile_cache
        assert CHECKOUT_CACHE_DIR.name == ".jax_cache"
        os.environ["JAX_COMPILATION_CACHE_DIR"] = "/elsewhere"
        assert enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None
        del os.environ["JAX_COMPILATION_CACHE_DIR"]
        assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
        print("OK")
    """)
    assert "OK" in out


def test_mesh_phase_on_four_virtual_devices():
    out = run_sub("""
        import importlib.util, json
        from pathlib import Path
        import tolerance
        from repro.config import PUBMED, reduced_graph
        path = Path(tolerance.__file__).resolve().parents[1] / "chip_smoke.py"
        s = importlib.util.spec_from_file_location("chip_smoke", path)
        smoke = importlib.util.module_from_spec(s)
        s.loader.exec_module(smoke)
        spec = reduced_graph(PUBMED, 301, 48)
        g, x = smoke._graph(spec)
        recs = smoke.phase_mesh(spec, g, x, jax.devices()[:4])
        assert [r["phase"] for r in recs] == ["mesh/one-chip",
            "mesh/ring/none", "mesh/ring/pipelined", "mesh/2d"]
        for r in recs[1:]:
            assert r["shard_devices"] == [0, 1, 2, 3], r
            assert r["collective_permute"], r
        assert recs[3]["reduce_scatter"]
        print(json.dumps(recs[-1]))
        print("OK")
    """)
    assert "OK" in out
