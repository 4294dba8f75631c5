"""Backend & runtime detection shared by the kernels and the planner.

One place answers three questions every execution path used to answer
ad-hoc (and sometimes wrongly, e.g. a hardcoded ``interpret=True``):

  * which platform are we on (``platform`` / ``on_tpu``)?
  * should a Pallas kernel run compiled or interpreted
    (``default_interpret``: compiled on a TPU, interpreted everywhere else
    so the whole suite runs on CPU containers; overridable via
    ``REPRO_PALLAS_INTERPRET``)?
  * which aggregation backend should a plan use when asked for "auto"
    (``resolve_backend``)?

Backends form two *tiers* (paper F3: a specialized aggregation kernel
beats the generic segmented reduction, and its shape follows the memory
hierarchy):

  * ``"xla"``        -- ``jax.ops.segment_sum``; the portable baseline and
    the resolution of "auto" everywhere but a TPU.
  * ``"pallas-tpu"`` -- the one-hot-MXU ``seg_agg`` kernel
    (kernels/seg_agg.py): sequential edge-chunk grid dimension with a VMEM
    scratch accumulator; collisions are impossible by construction.

The execution planner (core/plan.py) consults this module once at
plan-build time; kernels consult it only when a caller passes
``interpret=None``.
"""

from __future__ import annotations

import os
from typing import Optional

import jax

XLA = "xla"
PALLAS_TPU = "pallas-tpu"
AUTO = "auto"
BACKENDS = (XLA, PALLAS_TPU)


def platform() -> str:
    """The JAX default backend platform: "cpu" | "gpu" | "tpu"."""
    return jax.default_backend()


def on_tpu() -> bool:
    return platform() == "tpu"


def default_interpret() -> bool:
    """Pallas interpret mode: compiled on a TPU, interpreted elsewhere.

    ``REPRO_PALLAS_INTERPRET=0``/``1`` overrides the detection (e.g. to
    force interpret mode on a TPU while debugging a kernel, or to lower
    the kernels for Mosaic when compiling for a described TPU).
    """
    env = os.environ.get("REPRO_PALLAS_INTERPRET")
    if env is not None:
        return env != "0"
    return not on_tpu()


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """An explicit ``interpret`` as given, else ``default_interpret()``."""
    if interpret is not None:
        return bool(interpret)
    return default_interpret()


def default_machine(requested: str = AUTO):
    """Machine preset matching a (possibly unresolved) backend tier.

    Resolves the tier first (``resolve_backend``), then maps it to the
    preset the characterization subsystem models it with
    (``repro.profile.machine.machine_for_backend``).  Lets plan-level code
    stay machine-implicit until a caller overrides it.
    """
    from repro.profile.machine import machine_for_backend
    return machine_for_backend(resolve_backend(requested))


def resolve_backend(requested: str = AUTO) -> str:
    """Map a requested backend to a concrete tier (never "auto").

    Resolution table (paper F3 restated per platform)::

        requested      tpu          anything else (cpu, gpu)
        -----------    ----------   ------------------------
        "auto"         pallas-tpu   xla
        "xla" / "pallas-tpu"        (returned as requested)

    An explicit ``"pallas-tpu"`` off a TPU runs in interpret mode
    (``default_interpret``), so the tier stays testable on a CPU-only
    container.

    Example::

        >>> resolve_backend("xla")
        'xla'
        >>> resolve_backend()           # on a CPU container
        'xla'

    Raises ``ValueError`` for anything outside ``BACKENDS + (AUTO,)``.
    """
    if requested in BACKENDS:
        return requested
    if requested != AUTO:
        raise ValueError(
            f"unknown backend {requested!r}; expected one of "
            f"{BACKENDS + (AUTO,)}")
    return PALLAS_TPU if on_tpu() else XLA
