"""Machine: one dataclass describing the hardware a characterization targets.

The paper characterizes GCNs on a V100 and derives guidelines from that
machine's balance point; PRs 2-3 added a TPU tier and a GPU tier but left the
hardware numbers as module-level constants in ``core/characterize.py`` (TPU
v5e) plus a bag of ``GPU_*`` occupancy constants.  This module replaces both:
every roofline term, bound classification, tile picker, and ordering cost
model takes a ``Machine`` value instead of importing globals, so the same
analysis runs against any accelerator by passing a different preset.

Presets::

    TPU_V5E   197 TFLOP/s bf16, 819 GB/s HBM, 4x50 GB/s ICI, 128 MiB VMEM
    TPU_V5P   459 TFLOP/s bf16, 2765 GB/s HBM2e, 6x100 GB/s ICI (3-D
              torus), 128 MiB VMEM -- the multi-host scale-out target the
              distributed overlap model prices
    A100      312 TFLOP/s bf16, 1555 GB/s HBM, 12x25 GB/s NVLink,
              192 KiB SMEM/L1 carveout per SM (the GPU occupancy model)
    H100      989 TFLOP/s bf16, 3350 GB/s HBM3, 18x25 GB/s NVLink 4,
              228 KiB SMEM/L1 carveout per SM (the serving-tier GPU)
    V100      15.7 TFLOP/s fp32, 900 GB/s HBM -- the PAPER's machine; its
              balance point (~17.4 F/B) is the classification threshold
              behind Table 3's "Execution Bound" row.

The interconnect is described per hop -- ``interconnect_bw`` (one link's
bandwidth) plus ``link_latency_s`` (per-message launch latency) -- because
the ring halo schedules (``core.distributed``) saturate ONE link per
direction per hop; ``interconnect_total`` remains the aggregate all-links
number for bisection-style accounting.  ``hop_time(nbytes)`` is the
overlap model's per-hop wire term.

``machine_for_backend`` gives a resolved backend tier (``core.backend``)
the platform's preset so plan-level code can stay machine-implicit until
a caller overrides it.

``choose_dtype``/``dtype_model`` price the execution dtype the same way
``choose_overlap``/``overlap_model`` price halo pipelining: per-phase byte
and FLOP terms against THIS machine's HBM bandwidth, matmul peak at the
candidate precision (``native_bf16`` gates whether bf16 doubles or halves
the matmul rate), and -- when a partition is in play -- ``hop_time`` on the
reduced halo payload.  The resolved value feeds ``build_plan(dtype="auto")``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional


@dataclass(frozen=True)
class Machine:
    """Hardware description consumed by the characterization subsystem.

    Attributes:
      name: registry key ("tpu-v5e" | "a100" | "v100" | ...).
      kind: accelerator family, "tpu" | "gpu" (selects the occupancy model
        ``suggest_tile_m`` applies).
      peak_flops: peak matmul FLOP/s at the native precision the repo
        models (bf16 tensor/MXU for v5e/A100, fp32 CUDA cores for the
        paper's V100 numbers).
      hbm_bw: HBM bandwidth, bytes/s.
      interconnect_bw: per-link chip interconnect bandwidth, bytes/s
        (ICI link on TPU, NVLink lane on GPU) -- the PER-HOP bandwidth a
        ring collective sees (one link per direction per hop).
      interconnect_links: number of such links per chip.
      link_latency_s: per-message launch latency of one interconnect hop,
        seconds (the fixed term of ``hop_time``; ~1 us ICI, ~2 us NVLink
        with software overheads).
      on_chip_bytes: the fast scratch a fused tile must fit -- whole VMEM
        on TPU, the unified SMEM/L1 carveout per SM on GPU.
      regfile_bytes: register file per SM (GPU occupancy input; 0 on TPU).
      target_ctas: resident CTAs per SM needed to hide HBM latency (GPU
        occupancy input; 0 on TPU, where one sequential grid walks blocks).
      row_align: natural row granularity of a tile (8 sublanes on TPU,
        32 warp threads on GPU).
      matrix_tile: systolic/tensor tile edge for pad-waste accounting
        (128 MXU lanes on TPU).
      native_bf16: whether the matmul units run bf16 at ``peak_flops``
        (MXU / tensor cores: v5e, v5p, A100, H100).  False on the paper's
        V100, whose ``peak_flops`` is the fp32 CUDA-core rate -- there
        bf16 matmuls emulate through fp32 and gain nothing, which is what
        lets ``choose_dtype`` flip between presets on the same workload.
    """

    name: str
    kind: str
    peak_flops: float
    hbm_bw: float
    interconnect_bw: float
    interconnect_links: int
    on_chip_bytes: int
    link_latency_s: float = 1e-6
    regfile_bytes: int = 0
    target_ctas: int = 0
    row_align: int = 8
    matrix_tile: int = 128
    native_bf16: bool = True

    def __post_init__(self):
        assert self.kind in ("tpu", "gpu"), self.kind

    @property
    def balance(self) -> float:
        """Machine balance: FLOPs per HBM byte at which compute and memory
        time are equal.  AI below this is memory-bound (paper Table 3)."""
        return self.peak_flops / self.hbm_bw

    @property
    def interconnect_total(self) -> float:
        """Aggregate interconnect bandwidth (all links), bytes/s."""
        return self.interconnect_bw * self.interconnect_links

    def hop_time(self, nbytes: float) -> float:
        """Seconds for ONE interconnect hop moving ``nbytes`` over a single
        link: ``link_latency_s + nbytes / interconnect_bw``.  The per-hop
        wire term of the distributed overlap model
        (``core.distributed.overlap_model``) -- a ring collective's hop
        sees one link's bandwidth, never ``interconnect_total``."""
        return self.link_latency_s + nbytes / self.interconnect_bw

    def tile_budget(self) -> int:
        """On-chip bytes one fused tile may claim: half of VMEM on TPU
        (the other half double-buffers), an SM-carveout share per resident
        CTA on GPU (latency hiding comes from CTA count, not tile size)."""
        if self.kind == "gpu":
            return self.on_chip_bytes // max(1, self.target_ctas)
        return self.on_chip_bytes // 2

    def classify(self, arithmetic_intensity: float) -> str:
        """"memory" | "compute" bound classification against this balance."""
        return "memory" if arithmetic_intensity < self.balance else "compute"

    def matmul_peak(self, dtype: str = "f32") -> float:
        """Effective matmul FLOP/s at ``dtype`` on this machine.

        ``peak_flops`` is quoted at the native precision: bf16 for
        MXU/tensor-core parts (``native_bf16=True``), fp32 for the paper's
        V100.  bf16 on a non-native part emulates through the fp32 units
        (no gain); f32 on a native-bf16 part runs the matrix units at half
        rate.  ``int8-agg`` keeps combination in f32, so it prices as f32.
        """
        if dtype == "bf16":
            return self.peak_flops if self.native_bf16 \
                else self.peak_flops / 2
        return self.peak_flops / 2 if self.native_bf16 else self.peak_flops


#: TPU v5e, per chip (the repo's default modeling target since PR 1).
TPU_V5E = Machine(
    name="tpu-v5e", kind="tpu",
    peak_flops=197e12, hbm_bw=819e9,
    interconnect_bw=50e9, interconnect_links=4,     # 2-D torus: +-x, +-y
    on_chip_bytes=128 * 1024 * 1024,                # VMEM
    link_latency_s=1e-6,
    row_align=8, matrix_tile=128)

#: TPU v5p, per chip: the scale-out pod part (3-D torus, 6 ICI links at
#: ~100 GB/s each).  The Machine the distributed overlap model prices
#: multi-host halo pipelining against -- fatter links than v5e move the
#: choose_overlap break-even point.
TPU_V5P = Machine(
    name="tpu-v5p", kind="tpu",
    peak_flops=459e12, hbm_bw=2765e9,
    interconnect_bw=100e9, interconnect_links=6,    # 3-D torus: +-x,y,z
    on_chip_bytes=128 * 1024 * 1024,                # VMEM
    link_latency_s=1e-6,
    row_align=8, matrix_tile=128)

#: A100-SXM4 (bf16 tensor cores).  The occupancy fields are what the GPU
#: tile picker consumes: per-SM SMEM/L1 carveout shared by ``target_ctas``
#: resident blocks, warp-aligned rows.
A100 = Machine(
    name="a100", kind="gpu",
    peak_flops=312e12, hbm_bw=1555e9,
    interconnect_bw=25e9, interconnect_links=12,    # NVLink 3
    link_latency_s=2e-6,
    on_chip_bytes=192 * 1024,                       # unified SMEM/L1 per SM
    regfile_bytes=256 * 1024, target_ctas=4,
    row_align=32, matrix_tile=16)

#: H100-SXM5 (bf16 tensor cores, dense).  Same occupancy model as A100 with
#: Hopper's larger SMEM/L1 carveout and HBM3; its steeper balance point
#: (~295 F/B) pushes even more GCN phases memory-bound -- the machine the
#: serving benchmarks (``bench_serve``) price latency against.
H100 = Machine(
    name="h100", kind="gpu",
    peak_flops=989e12, hbm_bw=3350e9,
    interconnect_bw=25e9, interconnect_links=18,    # NVLink 4
    link_latency_s=2e-6,
    on_chip_bytes=228 * 1024,                       # unified SMEM/L1 per SM
    regfile_bytes=256 * 1024, target_ctas=4,
    row_align=32, matrix_tile=16)

#: V100 with the PAPER's numbers (fp32 CUDA-core peak / 900 GB/s HBM2):
#: balance ~17.4 F/B, the threshold behind Table 3's bound classification.
V100 = Machine(
    name="v100", kind="gpu",
    peak_flops=15.7e12, hbm_bw=900e9,
    interconnect_bw=25e9, interconnect_links=6,     # NVLink 2
    link_latency_s=2e-6,
    on_chip_bytes=128 * 1024,                       # unified SMEM/L1 per SM
    regfile_bytes=256 * 1024, target_ctas=4,
    row_align=32, matrix_tile=16,
    native_bf16=False)                              # fp32 CUDA-core peak

MACHINES: Dict[str, Machine] = {m.name: m
                                for m in (TPU_V5E, TPU_V5P, A100, H100, V100)}


def get_machine(name_or_machine) -> Machine:
    """Resolve a registry name (or pass a Machine through) to a Machine."""
    if isinstance(name_or_machine, Machine):
        return name_or_machine
    try:
        return MACHINES[name_or_machine]
    except KeyError:
        raise ValueError(f"unknown machine {name_or_machine!r}; "
                         f"known: {sorted(MACHINES)}") from None


#: ``device_kind`` (as JAX reports it) -> preset, for code running on a
#: real TPU.  A TPU whose kind is missing here is an error, not a v5e.
TPU_KINDS: Dict[str, Machine] = {"TPU v5 lite": TPU_V5E, "TPU v5": TPU_V5P}


def tpu_machine(device_kind: str) -> Machine:
    """The preset of a real TPU, keyed by its ``device_kind``."""
    try:
        return TPU_KINDS[device_kind]
    except KeyError:
        raise ValueError(f"no Machine preset for TPU kind {device_kind!r}; "
                         f"known: {sorted(TPU_KINDS)}") from None


def machine_for_backend(backend: Optional[str]) -> Machine:
    """Natural Machine preset for a resolved backend tier.

    Both tiers (``xla``, ``pallas-tpu``) map alike: on a real TPU, that
    chip's preset by ``device_kind`` (``tpu_machine``; an unknown kind
    raises); off-TPU, TPU_V5E, the repo's explicit modeling target.
    Callers wanting the paper's machine pass ``V100`` explicitly.
    """
    import jax
    device = jax.devices()[0]
    if device.platform == "tpu":
        return tpu_machine(device.device_kind)
    return TPU_V5E


# --------------------------------------------------------------------------
# Execution dtype as a priced decision (build_plan(dtype="auto"))
# --------------------------------------------------------------------------

#: storage bytes per element at each plan dtype.  ``int8-agg`` is the wire
#: and gather width of the AGGREGATION operand only -- combination stays
#: f32, which is why it never wins the auto decision and stays opt-in.
DTYPE_BYTES: Dict[str, int] = {"f32": 4, "bf16": 2, "int8-agg": 1}

#: minimum modeled fractional saving before ``choose_dtype`` leaves f32.
#: Mirrors ``core.distributed.OVERLAP_SAVING_THRESHOLD``: a sub-5% modeled
#: win is inside the model's noise and not worth the precision loss.
DTYPE_SAVING_THRESHOLD = 0.05


def dtype_model(num_vertices: int, num_edges: int, feature_len: int,
                out_len: Optional[int] = None, *,
                machine: Machine = None, num_shards: int = 1,
                dtypes=("f32", "bf16")) -> Dict[str, Dict[str, float]]:
    """Model per-layer time at each candidate execution dtype.

    Per dtype ``dt`` with element width ``B = DTYPE_BYTES[dt]`` (the
    aggregation operand width; combination activations use ``B`` except
    under ``int8-agg`` where combine stays f32):

    * aggregation (memory-bound, paper Table 3): gather ``E`` neighbor rows
      + read/write ``V`` rows at ``feature_len * B`` bytes each, plus the
      dtype-independent 8-byte edge indices -- all over ``hbm_bw``;
    * combination: ``2 * V * feature_len * out_len`` FLOPs at
      ``matmul_peak(dt)`` vs. its HBM traffic, whichever dominates;
    * halo (only when ``num_shards > 1``): ``num_shards - 1`` ring hops of
      one resident block (``ceil(V / num_shards)`` rows) at the reduced
      payload width, each priced by ``hop_time`` -- the wire is where
      bf16's exact 2x byte cut pays most;
    * ``tile_rows``: rows of width ``feature_len`` one ``tile_budget()``
      holds at this dtype -- the "reduced precision doubles the effective
      tile budget" term surfaced for ``bench_dtype``.

    Returns ``{dtype: {"agg_s", "combine_s", "halo_s", "total_s",
    "tile_rows"}}``.
    """
    machine = TPU_V5E if machine is None else get_machine(machine)
    out_len = feature_len if out_len is None else out_len
    v, e, f = float(num_vertices), float(num_edges), float(feature_len)
    out = {}
    for dt in dtypes:
        b = float(DTYPE_BYTES[dt])
        comb_b = 4.0 if dt == "int8-agg" else b
        agg_bytes = (e + 2.0 * v) * f * b + e * 8.0
        agg_s = agg_bytes / machine.hbm_bw
        flops = 2.0 * v * f * out_len
        comb_bytes = v * (f + out_len) * comb_b + f * out_len * comb_b
        comb_s = max(flops / machine.matmul_peak(dt),
                     comb_bytes / machine.hbm_bw)
        halo_s = 0.0
        if num_shards > 1:
            block = -(-num_vertices // num_shards)  # ceil
            halo_s = (num_shards - 1) * machine.hop_time(block * f * b)
        out[dt] = {
            "agg_s": agg_s, "combine_s": comb_s, "halo_s": halo_s,
            "total_s": agg_s + comb_s + halo_s,
            "tile_rows": float(machine.tile_budget() //
                               max(1, int(f * b))),
        }
    return out


def choose_dtype(num_vertices: int, num_edges: int, feature_len: int,
                 out_len: Optional[int] = None, *,
                 machine: Machine = None, num_shards: int = 1) -> str:
    """Resolve ``build_plan(dtype="auto")`` to ``"f32"`` or ``"bf16"``.

    Prices one layer via ``dtype_model`` -- HBM aggregation traffic,
    matmul peak at each precision (``Machine.native_bf16``), and, when
    sharded, ``Machine.hop_time`` on the halved halo payload -- and picks
    bf16 only when its modeled total beats f32 by at least
    ``DTYPE_SAVING_THRESHOLD``.  ``int8-agg`` is never auto-chosen: its
    quantization error is a semantic decision the caller must opt into.

    The decision provably flips across presets on one workload: a 256-node
    / ~1k-edge graph at 128->128 features is bf16 on ``TPU_V5E``/``A100``
    (native bf16 matmul, halved HBM bytes) but f32 on the paper's ``V100``
    (fp32 CUDA-core peak: bf16 would halve the matmul rate and the layer
    is combination-limited there).

    >>> choose_dtype(256, 1024, 128, machine=V100)
    'f32'
    >>> choose_dtype(256, 1024, 128, machine=TPU_V5E)
    'bf16'
    """
    model = dtype_model(num_vertices, num_edges, feature_len, out_len,
                        machine=machine, num_shards=num_shards,
                        dtypes=("f32", "bf16"))
    f32_s, bf16_s = model["f32"]["total_s"], model["bf16"]["total_s"]
    if f32_s <= 0:
        return "f32"
    return "bf16" if (f32_s - bf16_s) / f32_s >= DTYPE_SAVING_THRESHOLD \
        else "f32"


# --------------------------------------------------------------------------
# Pair-redundancy elimination as a priced decision (build_plan(dedup="auto"))
# --------------------------------------------------------------------------

#: minimum modeled fractional aggregation-time saving before
#: ``choose_dedup`` leaves the naive layout.  Mirrors
#: ``DTYPE_SAVING_THRESHOLD``: below this the two-level layout's extra
#: indirection is inside the model's noise.
DEDUP_SAVING_THRESHOLD = 0.05


def dedup_model(num_vertices: int, num_edges: int, feature_len: int, *,
                num_pairs: int, num_edges2: int,
                machine: Machine = None,
                dtype: str = "f32") -> Dict[str, Dict[str, float]]:
    """Model the aggregation phase naive vs. two-level dedup (graph/dedup.py).

    Aggregation is memory-bound on every preset (paper Table 3), so both
    layouts are priced as HBM slab traffic over ``machine.hbm_bw`` at the
    plan dtype's element width:

    * ``"none"``: gather ``E`` neighbor rows + read/write ``V`` rows
      (``feature_len * B`` bytes each) + ``E`` 8-byte edge indices — the
      same slab term ``dtype_model`` charges the phase.
    * ``"pairs"``: gather ``E2`` shortened-list rows + read ``2 * P`` pair
      members + write ``P`` partials (level 1) + the same ``V`` self
      read/write, plus the shortened index traffic and the pair-id
      indirection — the extra gather/indirection cost the eliminated edges
      must beat.

    ``num_pairs``/``num_edges2`` come from a concrete
    ``build_dedup_layout`` run on the block (matching is host-side and
    cheap, so ``"auto"`` prices the REAL layout, not an estimate).
    Returns ``{"none": {...}, "pairs": {...}}`` with ``agg_bytes``,
    ``agg_s``, ``flops`` and ``saving`` (fraction of naive time saved).
    """
    machine = TPU_V5E if machine is None else get_machine(machine)
    b = float(DTYPE_BYTES.get(dtype, 4))
    v, e, f = float(num_vertices), float(num_edges), float(feature_len)
    p, e2 = float(num_pairs), float(num_edges2)
    naive_bytes = (e + 2.0 * v) * f * b + e * 8.0
    dedup_bytes = (e2 + 3.0 * p + 2.0 * v) * f * b + e2 * 8.0 + 2.0 * p * 4.0
    naive_s = naive_bytes / machine.hbm_bw
    dedup_s = dedup_bytes / machine.hbm_bw
    saving = (naive_s - dedup_s) / naive_s if naive_s > 0 else 0.0
    return {
        "none": {"agg_bytes": naive_bytes, "agg_s": naive_s,
                 "flops": (e + v) * f, "saving": 0.0},
        "pairs": {"agg_bytes": dedup_bytes, "agg_s": dedup_s,
                  "flops": (p + e2 + v) * f, "saving": saving},
    }


def choose_dedup(num_vertices: int, num_edges: int, feature_len: int, *,
                 num_pairs: int, num_edges2: int,
                 machine: Machine = None, dtype: str = "f32") -> str:
    """Resolve ``build_plan(dedup="auto")`` to ``"none"`` or ``"pairs"``.

    Prices the block's REAL matching result (``dedup_model``) against this
    ``Machine``'s HBM bandwidth and picks ``"pairs"`` only when the modeled
    aggregation-time saving clears ``DEDUP_SAVING_THRESHOLD``.  The
    decision provably flips between workloads on one machine: a
    fanout-regular sampled block (hub-heavy — many destinations share
    their leading neighbor pair, so matching removes a large edge
    fraction) picks ``"pairs"``, while a sparse full-graph layer (pairs
    scarce — the shortened list barely shrinks but still pays the pair
    gather + partial write) stays ``"none"``.

    >>> choose_dedup(96, 128, 128, num_pairs=8, num_edges2=80,
    ...              machine=TPU_V5E)
    'pairs'
    >>> choose_dedup(96, 128, 128, num_pairs=2, num_edges2=126,
    ...              machine=TPU_V5E)
    'none'
    """
    if num_pairs <= 0:
        return "none"
    model = dedup_model(num_vertices, num_edges, feature_len,
                        num_pairs=num_pairs, num_edges2=num_edges2,
                        machine=machine, dtype=dtype)
    return "pairs" if model["pairs"]["saving"] >= DEDUP_SAVING_THRESHOLD \
        else "none"
