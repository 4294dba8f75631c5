"""repro.profile: the one characterization API.

Everything the paper calls *characterization* -- per-phase time/FLOP/byte
breakdowns (Tables 3-5), bound classification, roofline terms, benchmark
sweeps -- hangs off four surfaces:

  * ``Machine`` (machine.py): hardware presets (``TPU_V5E`` | ``TPU_V5P``
    | ``A100`` | ``H100`` | the paper's ``V100``); every cost model takes
    one instead of importing module-level constants, and the per-hop
    interconnect fields (``interconnect_bw``, ``link_latency_s``,
    ``hop_time``) price the distributed halo overlap decision.
  * ``InstrumentedPlan`` / ``WorkloadReport`` (instrument.py): wrap a
    ``GraphExecutionPlan`` (``plan.instrument(machine=...)``) so one forward
    pass records per-layer, per-phase FLOPs / bytes / wall time into a typed
    report with ``to_json()`` / ``to_markdown()`` renderers.
  * ``BenchSpec`` / ``run_specs`` (bench.py): declarative benchmark specs
    (graph x model x machine x sweep axis) executed by one shared harness
    that owns warmup, timing, CSV artifacts, and dry-run validation.
  * ``span`` / ``count`` / ``gauge`` (spans.py): the host spans and
    counters the planned forward and the serving engine record at their
    layer boundaries, read back with ``repro.profile.spans.spans()`` and
    ``counters()``.

One call end to end::

    report = build_plan(g, cfg, in_dim, classes).instrument(
        machine=A100).run_model(params, x)
    print(report.to_markdown())        # paper-style per-phase breakdown

Submodules avoid importing ``repro.core`` at module scope so ``repro.core``
internals (dataflow, characterize) may import presets from here without a
cycle; plan/phase types are imported lazily inside functions.
"""

from repro.profile.machine import (A100, H100, MACHINES, TPU_V5E, TPU_V5P,
                                   V100, Machine, get_machine,
                                   machine_for_backend)

__all__ = [
    "Machine", "TPU_V5E", "TPU_V5P", "A100", "H100", "V100", "MACHINES",
    "get_machine", "machine_for_backend",
    # lazy (instrument.py / bench.py):
    "InstrumentedPlan", "WorkloadReport", "PhaseRecord",
    "WorkloadReportError", "validate_report_dict",
    "BenchSpec", "BenchContext", "run_specs", "timeit", "write_csv",
    "bench_graph", "latency_percentiles",
    # lazy (spans.py; its reader ``spans()`` is reached through the
    # submodule, ``repro.profile.spans.spans``):
    "Span", "span", "record", "count", "gauge", "counters", "reset",
]

_LAZY = {
    "InstrumentedPlan": "repro.profile.instrument",
    "WorkloadReport": "repro.profile.instrument",
    "PhaseRecord": "repro.profile.instrument",
    "WorkloadReportError": "repro.profile.instrument",
    "validate_report_dict": "repro.profile.instrument",
    "BenchSpec": "repro.profile.bench",
    "BenchContext": "repro.profile.bench",
    "run_specs": "repro.profile.bench",
    "timeit": "repro.profile.bench",
    "write_csv": "repro.profile.bench",
    "bench_graph": "repro.profile.bench",
    "latency_percentiles": "repro.profile.bench",
    **{n: "repro.profile.spans" for n in ("Span", "span", "record", "count",
                                          "gauge", "counters", "reset")},
}


def __getattr__(name):
    # Lazy so `repro.core.*` can import the machine presets mid-init
    # without pulling the instrument/bench layers (which need core types).
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(name)
    import importlib
    return getattr(importlib.import_module(mod), name)
