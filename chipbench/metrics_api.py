"""What a per-layer metric's reader is handed.

A reader is a file ``metrics/<metric name>.py`` with one function,
``read(r: Readings) -> float | None``.  It returns None where it finds
nothing to read; the harness then leaves the metric out of the line.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

from chipbench import trace as tracing
from chipbench import work

#: the Pallas kernels' stable ``pallas_call`` names
AGG_KERNELS = ("seg_agg", "fused_agg_combine")


@dataclasses.dataclass
class Readings:
    ctx: Any        # harness.Context of the run
    outcome: Any    # harness.Outcome of the run
    peaks: Dict[str, Dict[str, Any]]

    @property
    def summary(self) -> Optional[tracing.Summary]:
        return self.ctx.summary

    @property
    def config(self) -> Dict[str, Any]:
        return self.ctx.config

    @property
    def window_count(self) -> int:
        """Units of work completed in the window (forwards, requests)."""
        return self.outcome.attempted - self.outcome.failed

    def span(self, name: str) -> List[float]:
        return self.ctx.spans.get(name, [])

    def peak(self) -> Dict[str, Any]:
        """The chip's peaks; a device missing from the table is an error."""
        import jax
        kind = jax.devices()[0].device_kind
        if kind not in self.peaks:
            raise KeyError(f"no peaks for device kind {kind!r} in "
                           "chipbench/peaks.json")
        return self.peaks[kind]

    def busy_s(self) -> Optional[float]:
        s = self.summary
        return s.busy_s if s is not None and s.busy_s > 0 else None

    def idle_pct(self) -> Optional[float]:
        s = self.summary
        if s is None or s.window_s <= 0:
            return None
        return 100.0 * (1.0 - s.busy_s / s.window_s)

    def kernel_s(self, kernels=AGG_KERNELS) -> Optional[float]:
        s = self.summary
        t = tracing.kernel_seconds(s, kernels) if s is not None else 0.0
        return t if t > 0 else None

    def least_forward_s(self) -> float:
        """Least seconds of one forward's compulsory work on this chip."""
        return work.least_time(work.forward_flops(self.config),
                               work.forward_bytes(self.config),
                               self.peak())[0]

    def least_agg_s(self) -> float:
        """Least seconds of one forward's aggregation work."""
        return work.least_time(work.agg_flops(self.config),
                               work.agg_bytes(self.config), self.peak())[0]
