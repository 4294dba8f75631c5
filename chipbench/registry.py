"""The program's in-memory span and counter registry, for the readers of
``program_span`` and ``program_counter`` metrics.

A checkout of the program that has no registry (``repro.profile.spans``)
reads as nothing: ``registry()`` is None and each reader returns None.
"""

from __future__ import annotations

from typing import Optional


def registry():
    """The module ``repro.profile.spans``, or None where the program has
    none."""
    try:
        from repro.profile import spans
    except ImportError:
        return None
    return spans


def layer_sum(r, counter: str) -> Optional[float]:
    """Sum over the model's layers of the program's gauge
    ``agg.<counter>.l<i>`` (set when the forward is traced); None where
    the program sets none."""
    reg = registry()
    if reg is None:
        return None
    values = reg.counters()
    keys = [f"agg.{counter}.l{i}"
            for i in range(r.config["model"]["num_layers"])]
    if not all(k in values for k in keys):
        return None
    return float(sum(values[k] for k in keys))
