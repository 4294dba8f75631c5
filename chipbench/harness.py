"""What every traffic kind shares: the run's context, its host spans, its
measured window (traced or not), and the device readings.

A kind module exposes ``run(ctx) -> Outcome``: it sets its cell up, opens
``ctx.window()`` for the timed work, and after the window compares what
the timed path produced with the plain reference.
"""

from __future__ import annotations

import contextlib
import dataclasses
import shutil
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

from chipbench import trace as tracing

#: a traced run's window is capped: the per-layer metrics need a steady
#: stretch, not the whole run, and reducing a trace of ~20k forwards a
#: minute runs past a run's time limit
TRACE_SECONDS = 10.0


@dataclasses.dataclass
class Check:
    """One number compared for ``correct``, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    metrics: Dict[str, float]              # end-to-end values, by name
    attempted: int
    failed: int
    checks: List[Check]
    counters: Dict[str, Any] = dataclasses.field(default_factory=dict)
    control: Dict[str, float] = dataclasses.field(default_factory=dict)


class CompileCounter:
    """Counts JAX's trace, lowering and compile events in this process
    (JAX's listeners are process-wide, so there is one counter)."""

    PREFIX = "/jax/core/compile/"
    count = 0
    _registered = False

    @classmethod
    def start(cls) -> int:
        if not cls._registered:
            import jax

            def listen(event, duration, **kw):
                if event.startswith(cls.PREFIX):
                    cls.count += 1
            jax.monitoring.register_event_duration_secs_listener(listen)
            cls._registered = True
        return cls.count


class Window:
    """The measured window: open while less than ``seconds`` have passed."""

    def __init__(self, seconds: float):
        self.seconds = float(seconds)
        self.start = time.perf_counter()
        self.end: Optional[float] = None

    def is_open(self) -> bool:
        return time.perf_counter() - self.start < self.seconds

    @property
    def elapsed(self) -> float:
        return (self.end or time.perf_counter()) - self.start


@dataclasses.dataclass
class Context:
    """One run of one cell."""

    cell: Dict[str, Any]                   # manifest.cell() of the run
    seed: int
    seconds: float
    trace: bool
    work_dir: Path                         # inside the checkout, git-ignored
    t0: float                              # process start, perf_counter
    control: bool = False                  # also read the control
    setup_end: Optional[float] = None
    spans: Dict[str, List[float]] = dataclasses.field(default_factory=dict)
    summary: Optional[tracing.Summary] = None
    memory_peak_bytes: Optional[int] = None
    compiles: int = 0

    @property
    def config(self) -> Dict[str, Any]:
        return self.cell["config_data"]

    @property
    def traffic(self) -> Dict[str, Any]:
        return self.cell["traffic_data"]

    def limit(self, name: str) -> float:
        return float(self.cell["limits"][name]["limit"])

    @contextlib.contextmanager
    def span(self, name: str):
        """Time one host span; in a traced run also write it into the
        profiler's trace, where idle gaps are attributed to it."""
        t = time.perf_counter()
        if self.trace:
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
        self.spans.setdefault(name, []).append(time.perf_counter() - t)

    def wrap(self, obj, method: str, name: str) -> None:
        """Put a span around one bound method of ``obj`` (the instance
        only: the program's class is untouched)."""
        inner = getattr(obj, method)

        def wrapped(*a, **kw):
            with self.span(name):
                return inner(*a, **kw)
        setattr(obj, method, wrapped)

    @contextlib.contextmanager
    def window(self):
        """Close set-up, then run the timed work; with ``trace`` under the
        profiler, reduced to ``self.summary`` when the window closes.
        ``self.compiles`` counts what JAX traced or compiled inside (it
        should stay 0)."""
        before = CompileCounter.start()
        self.setup_end = time.perf_counter()
        try:
            yield from self._timed()
        finally:
            self.compiles = CompileCounter.count - before

    def _timed(self):
        if not self.trace:
            w = Window(self.seconds)
            yield w
            w.end = time.perf_counter()
            return
        import jax
        trace_dir = self.work_dir / "trace"
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # the harness's spans are enough
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
                w = Window(min(self.seconds, TRACE_SECONDS))
                yield w
                w.end = time.perf_counter()
        finally:
            jax.profiler.stop_trace()
        devices, spans = tracing.read_xplane(tracing.find_xplane(
            str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)
        self.summary = tracing.summarize(devices, spans)

    def read_memory(self, devices) -> None:
        """Peak bytes in use on the fullest device (read before the
        reference runs: a process's peak never falls again)."""
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in devices]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak_bytes = max(peaks) if peaks else None


def model_config(cfg: Dict[str, Any]):
    """The program's model configuration, from the config file."""
    from repro.config import GCNModelConfig
    m = cfg["model"]
    return GCNModelConfig(cfg["name"], conv=m["conv"],
                          aggregator=m["aggregator"],
                          hidden_dims=tuple(m["hidden_dims"]),
                          ordering=m["ordering"], fused=m["fused"],
                          num_layers=m["num_layers"])


def program_graph(cfg: Dict[str, Any]) -> Tuple[Any, Any, Any]:
    """``(graph the program takes, src, dst)`` of the configuration."""
    from repro.graph.structure import graph_from_coo
    from chipbench.inputs import graph_coo
    src, dst = graph_coo(cfg["graph"])
    return graph_from_coo(src, dst, cfg["graph"]["num_vertices"]), src, dst


def free_program() -> None:
    """Drop the program's cached plans and every compiled executable, so
    the reference has the device to itself."""
    import gc

    import jax
    from repro.core.plan import clear_plan_cache
    clear_plan_cache()
    jax.clear_caches()
    gc.collect()
