"""Shared kernel utilities."""
from __future__ import annotations

import os
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.utils import faultinject, telemetry

# Scoped VMEM a pallas_call may use without raising its own limit, per TPU
# ``device_kind`` (source: the Pallas TPU documentation, "Pipelining" /
# ``vmem_limit_bytes``: 16 MiB by default on v4 and v5e, 32 MiB on v6e).  A
# TPU kind missing here is an error, never a default.  Off the chip the
# v5e figure is used, so CPU rehearsals resolve the same layouts as v5e.
# REPRO_VMEM_BUDGET_BYTES or the ``budget_bytes`` kwargs override it.
VMEM_BUDGET_BY_KIND = {
    "TPU v4": 16 << 20,
    "TPU v5 lite": 16 << 20,
    "TPU v6 lite": 32 << 20,
}
DEFAULT_VMEM_BUDGET_BYTES = VMEM_BUDGET_BY_KIND["TPU v5 lite"]
VMEM_BUDGET_ENV = "REPRO_VMEM_BUDGET_BYTES"

# Largest integer float32 accumulates exactly (24-bit mantissa).  Volume /
# modularity sums approach this once m_valid · max-weight nears it: every
# add past 2^24 can round away an entire unit-weight edge, silently biasing
# Q at com-orkut scale (117M directed edges, Table I).
F32_ACCUM_SAFE = 1 << 24

TABLE_MODES = ("auto", "resident", "streamed")

# Table padding / window-offset granularity (the TPU lane width): resident
# table scratch is padded to a multiple of this, and streamed window offsets
# are multiples of the per-bucket slot stride, itself a multiple of this.
TABLE_LANE = 128


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU (read at trace time)."""
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """Pallas interpret mode: on the CPU only.  The kernels are Mosaic TPU
    kernels, so any other backend is an error rather than a quiet
    emulation."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(
        f"Pallas TPU kernels cannot run on the {backend!r} backend")


def default_vmem_budget() -> int:
    """The scoped VMEM budget of the attached chip (``VMEM_BUDGET_BY_KIND``),
    or the v5e figure off the chip."""
    if not on_tpu():
        return DEFAULT_VMEM_BUDGET_BYTES
    kind = jax.devices()[0].device_kind
    if kind not in VMEM_BUDGET_BY_KIND:
        raise RuntimeError(
            f"no VMEM budget known for TPU device_kind {kind!r}; add it to "
            f"VMEM_BUDGET_BY_KIND or set {VMEM_BUDGET_ENV}")
    return VMEM_BUDGET_BY_KIND[kind]


def vmem_budget_bytes(budget_bytes: int | None = None) -> int:
    """Resolve the per-core VMEM byte budget.

    Precedence: explicit kwarg > REPRO_VMEM_BUDGET_BYTES env var > the
    per-device_kind budget (``default_vmem_budget``).  Read at trace time,
    so the resident/streamed decision and row-block sizing are static per
    compiled program.
    """
    if budget_bytes is not None:
        b = int(budget_bytes)
    else:
        env = os.environ.get(VMEM_BUDGET_ENV)
        b = int(env) if env else default_vmem_budget()
    if faultinject.is_active("vmem_starve"):
        # fault injection: collapse the budget so every capacity-adaptive
        # policy (resident/streamed tables, kernel/ref bin rank) lands in
        # its fallback regime — those regimes are bit-identical by the
        # parity contracts, which is exactly what tests/test_faults.py
        # asserts.  Callers arming this fault key their traces on it
        # (EngineSpec.faults), so a clean-cached trace is never reused.
        telemetry.bump("fault.vmem_starve.budget_clamped")
        b = min(b, 1024)
    return b


def accum_needs_promotion(m_cap: int, w_max: float = 1.0) -> bool:
    """Trace-time predicate for the volume/modularity precision guard:
    True when ``m_cap`` edge weights of magnitude ``w_max`` could sum past
    float32's exact-integer range.  Uses the static edge CAPACITY (an upper
    bound on m_valid), so the decision needs no device sync and is part of
    the compiled program's cache key."""
    return float(m_cap) * max(float(w_max), 1.0) >= float(F32_ACCUM_SAFE)


def accum_dtype(promote: bool):
    """Accumulator dtype for volume/modularity sums.

    float64 only when promotion is requested AND x64 is enabled; otherwise
    float32 with a telemetry bump (``numeric.f32_accum_risk``) so the risk
    is observable — the drivers surface it as a ``RunReport`` warning."""
    if not promote:
        return jnp.float32
    if jax.config.jax_enable_x64:
        return jnp.float64
    telemetry.bump("numeric.f32_accum_risk")
    return jnp.float32


def pick_row_block(width: int, budget_elems: int = 1 << 21,
                   max_rows: int = 512) -> int:
    """Rows per block so the (R_blk, W, W) pairwise tensor stays within a
    ~8 MB f32 VMEM budget; sublane-aligned."""
    r = max(1, budget_elems // max(1, width * width))
    r = min(r, max_rows)
    if r >= 8:
        r = (r // 8) * 8
    return r


def pick_row_block_fused(width: int, budget_bytes: int | None = None,
                         table_bytes: int = 0) -> int:
    """Row block for the gather-in-kernel local_move grid.

    Unlike the scored-tile kernels, the fused kernel receives no gathered
    (R_blk, W) input tiles — its per-step VMEM footprint is the (R_blk, W, W)
    pairwise tensor plus whatever table state is resident — so narrow buckets
    can afford much taller blocks.  Fewer grid steps amortize the table
    residency (and, in interpret mode, the per-step dispatch).

    ``table_bytes`` (the resident table scratch, or the streamed double-
    buffered windows) is charged against half the VMEM budget before sizing
    the pairwise tensor; the other half is reserved for Pallas's
    double-buffered tile pipeline.  With the default budget and no tables
    this reduces to the historical ~8 MB pairwise budget.  The pairwise
    budget is floored at budget//8: when the tables ALONE bust the half
    budget the layout cannot fit VMEM no matter the row block (that regime
    is streamed-or-bust), so collapsing to 1-row grid steps would add a
    pathological grid without recovering anything.
    """
    budget = vmem_budget_bytes(budget_bytes)
    avail = max(budget // 2 - table_bytes, budget // 8)
    return pick_row_block(width, max(1, avail // 4), max_rows=2048)


# Static width menu for the cascade's traced coarse-level re-bucketing
# (DESIGN.md §Pipeline).  A small menu keeps the number of distinct compiled
# stage programs bounded: each cascade stage picks ONE width from it.
STAGE_WIDTH_MENU = (16, 64, 256)


def pick_ell_width(max_deg: int | None, n_cap: int, m_cap: int) -> int:
    """Static ELL width for one cascade stage's traced re-bucketing.

    ``max_deg`` is the carried coarse graph's max unweighted degree, read at
    the stage boundary sync; the pick is the smallest menu width covering it
    (no tail pass at stage entry).  Hubs appearing at DEEPER levels inside
    the stage — or exceeding the widest menu entry — fall back to the
    engine's cond-gated edge-list tail, so the width only affects
    performance, never results.  ``max_deg=None`` (stage 0's coarse loop,
    before any boundary sync has run) uses a 4×-average-degree heuristic
    derived from the static stage capacities.
    """
    if max_deg is None:
        max_deg = max(STAGE_WIDTH_MENU[0], (4 * m_cap) // max(1, n_cap))
    for width in STAGE_WIDTH_MENU:
        if max_deg <= width:
            return width
    return STAGE_WIDTH_MENU[-1]


# ------------------------------------------------------------ capacity buckets

# Static capacity menu for the batched many-graph engine (DESIGN.md
# §Serving): doubling steps UP from ego-net-scale floors.  Graphs are
# padded up to the smallest menu capacity that holds them, so the set of
# distinct padded shapes — and with it the set of compiled batch programs —
# grows logarithmically in the largest graph served, not linearly in the
# number of distinct graph sizes.
#
# The step is 2 (not the cascade's shrink=4) and the floors sit well below
# the cascade floors ON PURPOSE: padding is pure wasted compute for every
# lane of a batch (a vmapped sweep touches every padded slot), so the menu
# bounds the waste at <2× worst-case / ~1.4× expected, where a quarter-step
# menu anchored at (256, 2048) inflates ego-net-sized graphs (n≈30-100,
# m≈100-600) by up to an order of magnitude.  The cost of the finer menu is
# only more compiled programs — still logarithmic, still LRU-bounded.
BUCKET_N_FLOOR = 64
BUCKET_M_FLOOR = 256
BUCKET_STEP = 2


def bucket_capacity(x: int, floor: int, step: int = BUCKET_STEP) -> int:
    """Smallest menu capacity >= x, menu = floor · step^k (k >= 0)."""
    if x < 0:
        raise ValueError(f"capacity must be >= 0, got {x}")
    cap = int(floor)
    while cap < x:
        cap *= step
    return cap


class CapacitySignature(NamedTuple):
    """Hashable identity of one compiled batch program (DESIGN.md §Serving).

    Two graphs with equal signatures pack into the same bucket and run under
    the SAME cached compiled program: ``n_cap``/``m_cap`` are the padded
    static capacities (the array shapes), ``ell_width`` the traced-ELL menu
    width those capacities pick (the ell/pallas tile shape), and
    ``schedule`` the capacity schedule the padded graph would cascade
    through — all static trace inputs, so equal signatures imply equal
    traces.
    """

    n_cap: int
    m_cap: int
    ell_width: int
    schedule: tuple


def capacity_signature(n_cap: int, m_cap: int,
                       ell_width: int | None = None,
                       schedule: tuple | None = None) -> CapacitySignature:
    """Bucket a graph's (n_max, m_max) onto the static capacity menu.

    Reuses the existing static menus end to end: capacities quantize onto
    the doubling menu above, ``ell_width``
    defaults to the ``pick_ell_width`` menu pick at the bucket capacities
    (``pick_bin_width`` resolves identically, so the aggregation bin width
    is covered by the same field), and ``schedule`` defaults to the bounded
    ``auto_capacity_schedule`` at the bucket capacities.
    """
    nb = bucket_capacity(int(n_cap), BUCKET_N_FLOOR)
    mb = bucket_capacity(int(m_cap), BUCKET_M_FLOOR)
    if ell_width is None:
        ell_width = pick_ell_width(None, nb, mb)
    if schedule is None:
        # late import: core.louvain imports this module at load time
        from repro.core.louvain import auto_capacity_schedule

        schedule = auto_capacity_schedule(nb, mb)
    return CapacitySignature(nb, mb, int(ell_width), tuple(schedule))


# ------------------------------------------------------- distributed capacity

# Per-shard partial-coarsen capacity floor: below this the fixed-cost terms
# (collective latency, program dispatch) dominate any memory win, so shards
# never shrink their partial-edge buffers past it.
HALO_CAP_FLOOR = 256


def pick_halo_cap(m_pad: int, n_devices: int) -> int:
    """Static per-shard capacity for partial coarse edge lists (DESIGN.md §6).

    Each device's partial coarsening of its local shard emits at most
    ``m_pad`` distinct (community, community) edges, but real graphs shrink
    ≥4× per level; half the shard capacity is a comfortable bound with a 2×
    memory/communication win.  The merged coarse capacity is then
    ``n_devices · cap`` — the gathered partial lists — which replaces the
    replicated ``n_devices · m_pad`` edge list.  Overflow past the cap is
    detected on device (a psum'd flag) and handled by the host degradation
    ladder (retry with replicated coarsening), so the cap affects memory and
    communication, never results.  Sublane-aligned like ``m_pad`` itself.
    """
    if m_pad <= 0 or n_devices <= 0:
        raise ValueError(f"need positive m_pad/n_devices, got {m_pad}/{n_devices}")
    cap = max(HALO_CAP_FLOOR, m_pad // 2)
    # never exceed the shard capacity itself: partial lists are static
    # [:cap] slices of m_pad-length buffers
    return min(int(m_pad), int(cdiv(cap, 8) * 8))


# Wire-format byte widths for the comm model: one edge is (src:int32,
# dst:int32, w:float32) plus a 1-byte validity mask; one label word is int32.
EDGE_WIRE_BYTES = 13
LABEL_WIRE_BYTES = 4


def dist_comm_bytes_per_level(n: int, m_pad: int, h_cap: int,
                              n_devices: int) -> dict:
    """Analytic per-level collective payload (bytes) of both coarsening modes.

    ``replicated`` moves the full padded edge list to every device once
    (the gather-then-replicate baseline: D·m_pad edges on the wire);
    ``shard_local`` moves only the two-phase contiguization table (n label
    words + D stripe counts) and the gathered partial coarse lists
    (D·h_cap edges) — O(boundary + communities), not O(m).
    """
    return {
        "replicated": n_devices * m_pad * EDGE_WIRE_BYTES,
        "shard_local": (n * LABEL_WIRE_BYTES
                        + n_devices * LABEL_WIRE_BYTES
                        + n_devices * h_cap * EDGE_WIRE_BYTES),
    }


# ---------------------------------------------------------------- aggregation

BIN_IMPLS = ("auto", "kernel", "ref")

# Kernels whose in-kernel gathers Mosaic refuses on the TPU, with the
# compiler's own message (JAX 0.9.0 / libtpu 0.0.34, v5e).  Mosaic lowers a
# gather only as a same-shape 2-D ``take_along_axis``; these index a table
# with arbitrary ids.  They stay the interpret-mode parity oracles on CPU,
# and no TPU policy below ever picks them: an explicit request raises.
TPU_UNLOWERABLE = {
    "bin_rank": "NotImplementedError: Only 2D gather is supported",
    "local_move.resident": "NotImplementedError: Only 2D gather is supported",
    "local_move.streamed": "NotImplementedError: Only 2D gather is supported",
}


def require_lowerable(kernel: str) -> None:
    """Raise when ``kernel`` is asked for on a TPU it cannot be compiled
    for (``TPU_UNLOWERABLE``); a no-op elsewhere."""
    if on_tpu() and kernel in TPU_UNLOWERABLE:
        raise ValueError(
            f"the {kernel} Pallas kernel does not lower on TPU "
            f"(Mosaic: {TPU_UNLOWERABLE[kernel]})")


def pick_bin_width(n_cap: int, m_cap: int) -> int:
    """Static per-src-community bin-row width for the sort-free aggregation
    (DESIGN.md §Aggregation kernel).

    Rows hold the DISTINCT destination communities of one source community,
    so the width must cover the coarse graph's out-degree, which is unknown
    at trace time; the pick reuses the cascade's 4×-average-degree heuristic
    over the STAGE capacities (same menu as the traced ELL re-bucketing, so
    the number of distinct compiled programs stays bounded).  Rows that
    exceed the width at runtime fall back to the one-sort path via a
    ``lax.cond`` gate — the width only affects performance, never results.
    """
    return pick_ell_width(None, n_cap, m_cap)


def resolve_bin_impl(impl: str) -> str:
    """Kernel-vs-ref policy for the binned aggregation rank pass.

    ``auto`` always resolves to the pure-jnp ref path: the kernel's row
    gather does not lower on TPU (``TPU_UNLOWERABLE``), and off the chip
    interpret-mode emulation would only add per-grid-step dispatch
    overhead.  ``kernel`` is the explicit interpret-mode oracle; asking for
    it on a TPU raises.
    """
    if impl not in BIN_IMPLS:
        raise ValueError(f"unknown bin impl {impl!r}, want one of {BIN_IMPLS}")
    if impl == "kernel":
        require_lowerable("bin_rank")
        return impl
    return "ref"


def resolve_table_mode(mode: str, table_bytes: int,
                       budget_bytes: int | None = None) -> str:
    """Resident-vs-streamed policy for the local_move per-vertex tables.

    ``auto`` keeps the tables VMEM-resident while they fit HALF the VMEM
    budget (the other half covers the pairwise tensor and the
    double-buffered tile pipeline) and streams per-block windows beyond
    that: resident  iff  table_bytes <= vmem_budget_bytes() // 2.
    """
    if mode not in TABLE_MODES:
        raise ValueError(f"unknown table_mode {mode!r}, want one of {TABLE_MODES}")
    if mode != "auto":
        return mode
    return ("resident" if table_bytes <= vmem_budget_bytes(budget_bytes) // 2
            else "streamed")


def hash_u32_jnp(x: jax.Array) -> jax.Array:
    """splitmix32 avalanche — identical to core.common.hash_u32 (kept local so
    kernels do not import the algorithm layer)."""
    x = x.astype(jnp.uint32)
    x = (x ^ (x >> 16)) * jnp.uint32(0x7FEB352D)
    x = (x ^ (x >> 15)) * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def tie_noise_jnp(a: jax.Array, b: jax.Array, seed: jax.Array, eps: float) -> jax.Array:
    h = hash_u32_jnp(
        a.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
        ^ hash_u32_jnp(b.astype(jnp.uint32) + seed.astype(jnp.uint32))
    )
    return u32_to_f32(h) * jnp.float32(eps / 4294967296.0)


def u32_to_f32(h: jax.Array) -> jax.Array:
    """``h.astype(float32)`` for uint32 ``h``, bit for bit, from two exact
    16-bit halves: their sum rounds once, exactly as the direct convert
    does.  Mosaic has no uint32 → float32 cast."""
    hi = (h >> 16).astype(jnp.int32).astype(jnp.float32) * jnp.float32(65536.0)
    lo = (h & jnp.uint32(0xFFFF)).astype(jnp.int32).astype(jnp.float32)
    return hi + lo
