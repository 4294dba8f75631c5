"""The ONE per-dtype tolerance table for the whole suite.

The reduced-precision plan contract (core/plan.py ``build_plan(dtype=)``)
is two-sided: f32 plans are BITWISE-golden (no tolerance at all), reduced
dtypes are equivalent within a band that is a property of the *dtype*, not
of the individual test.  Ad-hoc ``atol=``/``rtol=`` literals scattered
through test files hide which side of that contract a comparison sits on
-- and drift independently when someone loosens one.  So the bands live
here, once:

  * ``f32``      -- (1e-5, 1e-5): accumulation-order noise only (different
    reduction shapes between a kernel and its jnp oracle, or between the
    eager and the jitted program, which XLA may fuse differently).  Eager
    and ``plan.compile()`` outputs are each compared with the plain float32
    oracle (``assert_matches_reference``).  ``bitwise=True`` is kept for
    exactness that is structural: zero pad rows, the dedup fold order, the
    two halo schedules.
  * ``bf16``     -- (3e-2, 3e-2): 8-bit mantissa storage at phase
    boundaries, f32 accumulation.
  * ``int8-agg`` -- (2e-2, 2e-2): per-row symmetric int8 grid on the
    aggregation operand only (phases.quantize_int8), f32 everywhere else.

``scale`` expresses a test-specific slack factor (deeper compositions
accumulate more rounding) while keeping the base band shared -- a reviewer
reads ``scale=10`` as "10x the dtype's unit band", not a fresh magic
number.  Tests import this module directly (``import tolerance``; tests/
has no __init__.py so pytest puts this directory on sys.path) or take the
``tol`` fixture from conftest.
"""

from __future__ import annotations

import numpy as np

#: dtype -> (rtol, atol) unit band.  Keys are the plan-dtype vocabulary.
DTYPE_BANDS = {
    "f32": (1e-5, 1e-5),
    "bf16": (3e-2, 3e-2),
    "int8-agg": (2e-2, 2e-2),
}


def _band_key(dtype) -> str:
    """Normalize a plan-dtype string or an array dtype to a band key."""
    if isinstance(dtype, str) and dtype in DTYPE_BANDS:
        return dtype
    name = np.dtype(dtype).name if not hasattr(dtype, "name") else dtype.name
    # jnp.bfloat16 has dtype name "bfloat16"; jnp.float32 -> "float32"
    if "bfloat16" in str(name):
        return "bf16"
    if "float32" in str(name):
        return "f32"
    if "int8" in str(name):
        return "int8-agg"
    raise KeyError(f"no tolerance band for dtype {dtype!r}")


def assert_allclose_dtype(actual, desired, dtype="f32", *, scale: float = 1.0,
                          bitwise: bool = False, err_msg: str = "") -> None:
    """Assert equivalence at the dtype's shared band (or bitwise).

    ``dtype`` is a plan-dtype string ("f32" | "bf16" | "int8-agg") or an
    array dtype (jnp.float32 / jnp.bfloat16).  ``bitwise=True`` asserts
    exact equality regardless of dtype (structural exactness only).
    ``scale`` multiplies both rtol and atol.
    """
    a = np.asarray(actual, np.float32)
    d = np.asarray(desired, np.float32)
    if bitwise:
        np.testing.assert_array_equal(a, d, err_msg=err_msg)
        return
    rtol, atol = DTYPE_BANDS[_band_key(dtype)]
    np.testing.assert_allclose(a, d, rtol=rtol * scale, atol=atol * scale,
                               err_msg=err_msg)


def assert_matches_reference(actual, g, cfg, params, x, *, dtype="f32",
                             scale: float = 1.0, err_msg: str = "") -> None:
    """A planned forward's output against the plain float32 oracle
    (``repro.kernels.ref.gcn_forward_ref``, matmuls at "highest") on the
    same graph, params and features, within ``dtype``'s band."""
    from repro.kernels.ref import gcn_forward_ref
    ref = gcn_forward_ref(g.src, g.dst, g.num_vertices, cfg, params, x)
    assert_allclose_dtype(actual, ref, dtype, scale=scale, err_msg=err_msg)
