"""The benchmark's inputs: the graph of a configuration, and the features
and weights of a run, all made here from seeds.

The graph is the configuration's dataset: fixed by ``graph.graph_seed`` in
its file, the same in every run, as a real dataset is (and so every run
does the same work and compiles the same program).  Features and weights
come from the run's ``--seed``, made on the device in one jitted call.

The generator is the repository's ``graph/datasets.make_synthetic_graph``
(power-law sources, uniform destinations, self-loops moved off, ids
permuted), copied so that the program cannot change the yardstick.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

#: one graph per (config name, graph_seed) per process
_GRAPHS: Dict[Tuple, Tuple[np.ndarray, np.ndarray]] = {}


def graph_coo(gspec: dict) -> Tuple[np.ndarray, np.ndarray]:
    """``(src, dst)`` int32 edge arrays of the configuration's graph."""
    key = (gspec["num_vertices"], gspec["num_edges"], gspec["alpha"],
           gspec["graph_seed"])
    if key not in _GRAPHS:
        _GRAPHS[key] = _power_law_graph(*key)
    return _GRAPHS[key]


def _power_law_graph(v: int, e: int, alpha: float, seed: int):
    rng = np.random.default_rng(seed)
    cdf = np.cumsum(np.arange(1, v + 1, dtype=np.float64) ** -alpha)
    cdf /= cdf[-1]
    src = np.searchsorted(cdf, rng.random(e))
    dst = rng.integers(0, v, size=e)
    loop = src == dst
    src[loop] = (src[loop] + 1) % v
    perm = rng.permutation(v)
    return perm[src].astype(np.int32), perm[dst].astype(np.int32)


def prng_key(seed: int):
    """A JAX key from any whole number (the driver's seeds pass 2**31)."""
    import jax
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"--seed must be a whole number >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def device_inputs(seed: int, num_vertices: int, feature_len: int,
                  param_shapes):
    """``(params, x)`` on the device from ``seed``, in one jitted call.

    ``param_shapes`` is the program's parameter tree as shapes only
    (``jax.eval_shape`` of its ``init``): the layout is the program's, the
    numbers are the benchmark's.  Features are standard normal over
    ``sqrt(F)``; a weight matrix is normal times ``sqrt(2 / fan_in)``, a
    bias normal times 0.1 (a zero bias would leave bias handling
    unchecked).
    """
    import jax
    import jax.numpy as jnp

    leaves, treedef = jax.tree_util.tree_flatten(param_shapes)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(leaves) + 1)
        x = jax.random.normal(keys[0], (num_vertices, feature_len),
                              jnp.float32) / np.sqrt(feature_len)
        out = []
        for k, s in zip(keys[1:], leaves):
            scale = np.sqrt(2.0 / s.shape[0]) if len(s.shape) == 2 else 0.1
            out.append(jax.random.normal(k, s.shape, s.dtype) * scale)
        return jax.tree_util.tree_unflatten(treedef, out), x

    return make(prng_key(seed))
