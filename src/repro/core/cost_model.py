"""Analytical cost model — reproduces the paper's Tables I/II and Fig. 8.

All REPORTED numbers use the paper's published constants:

  * Table I op mix for a W=4 CAS: NOR 14, NOT 8, AND 3, COPY 3 (28 cycles);
    single-stage totals for N=8: NOR 84, NOT 48, AND 18, COPY 42 (192).
  * 0.55 ns per IMC operation at 65 nm (=> 1.81 GHz operating frequency).
  * Fig. 8 comparison baselines: MemSort (memristive IMC, [7]) and an
    off-memory (von Neumann) path.  This paper does not reprint [7]'s raw
    tables, so the MemSort model is anchored to the ratios the paper reports
    (1.45x cycles, 3.4x latency, and 5x vs the off-memory approach) — see
    DESIGN.md §6.

The per-cycle simulator (gates.py / sorter.py) validates FUNCTIONAL
correctness and total cycle counts; this module owns every latency /
throughput / comparison number quoted in EXPERIMENTS.md.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional

from repro.core import cas, network
from repro.core import tuning as _tuning
# Re-export: the constants' home is the tuning layer (the cost model
# *consumes* measured profiles, it does not own them), but every historical
# consumer spells cost_model.DeviceSortConstants.
from repro.core.tuning import DeviceSortConstants  # noqa: F401

# ---- paper constants (§III, Table I/II) -------------------------------------
CYCLE_NS = 0.55                      # latency of one IMC operation, 65 nm
OPERATING_FREQ_GHZ = 1 / CYCLE_NS    # 1.81 GHz (Table II)

TABLE1_CAS_OPS: Dict[str, int] = {"NOR": 14, "NOT": 8, "AND": 3, "COPY": 3}
CAS_CYCLES_W4 = sum(TABLE1_CAS_OPS.values())            # 28

# Fig. 8 anchors (ratios as published)
MEMSORT_CYCLE_RATIO = 1.45           # Fig. 8(a): cycles(MemSort)/cycles(ours)
MEMSORT_LATENCY_RATIO = 3.4          # Fig. 8(b)
OFF_MEMORY_LATENCY_RATIO = 5.0       # §III text


def cas_cycles(width: int = 4, use_paper_counts: bool = True) -> int:
    """Cycles for one CAS block.  W=4 is the paper's 28; other widths use the
    reconstructed gate program's length (extrapolation)."""
    if width == 4 and use_paper_counts:
        return CAS_CYCLES_W4
    return cas.cached_program(width).total_cycles


def sort_cycles(n: int, width: int = 4, use_paper_counts: bool = True) -> int:
    """Total cycles to sort N unsigned W-bit values in-memory.

    stages x CAS + movement (Eq. 3-4 with the paper's fused-first-exchange
    accounting).  N=8, W=4 -> 6*28 + 24 = 192 (§III / Table I).
    """
    stages = network.n_stages(n)
    movement = network.total_extra_cycles(n)
    return stages * cas_cycles(width, use_paper_counts) + movement


def sort_latency_ns(n: int, width: int = 4) -> float:
    """N=8, W=4 -> 105.6 ns (Table II)."""
    return sort_cycles(n, width) * CYCLE_NS


def throughput_gops(n: int, width: int = 4) -> float:
    """IMC operations per second; Table II reports 1.8 GOPS for N=8, W=4
    (one op per 0.55 ns cycle)."""
    return sort_cycles(n, width) / sort_latency_ns(n, width)


def stage_op_totals(n: int = 8) -> Dict[str, int]:
    """Table I right column: per-op totals for the complete N-input unit.

    Movement cycles are COPY-class (temp-row transfers): for N=8 the paper
    reports COPY 42 = 6 stages * 3 + 24 movement cycles.
    """
    stages = network.n_stages(n)
    totals = {k: v * stages for k, v in TABLE1_CAS_OPS.items()}
    totals["COPY"] += network.total_extra_cycles(n)
    return totals


# ---- comparison baselines (Fig. 8) ------------------------------------------

def memsort_cycles(n: int = 8, width: int = 4) -> float:
    return sort_cycles(n, width) * MEMSORT_CYCLE_RATIO


def memsort_latency_ns(n: int = 8, width: int = 4) -> float:
    return sort_latency_ns(n, width) * MEMSORT_LATENCY_RATIO


def off_memory_latency_ns(n: int = 8, width: int = 4) -> float:
    return sort_latency_ns(n, width) * OFF_MEMORY_LATENCY_RATIO


def bubble_sort_comparisons(n: int = 8) -> int:
    """Software baseline the paper uses (8-bit masked to 4-bit, bubble sort):
    worst-case compare-swap count."""
    return n * (n - 1) // 2


def memory_bits(n: int = 8, width: int = 4) -> int:
    """Fig. 8(c): array bits used, with CAS-row reuse (22-row array)."""
    from repro.core import sorter
    return sorter.array_geometry(n, width)["bits"]


# ---- device-level cost model (engine auto-dispatch) --------------------------
#
# The paper's model prices one SRAM macro; the engine's planner needs the same
# kind of closed form one level up: how long does each *device* backend take
# to sort (batch, n)?  Asymptotics are fixed per backend; the per-element
# constants and kernel shape parameters (radix digit width, histogram tile)
# live in the active ``repro.core.tuning`` profile — coarse per-platform
# defaults until ``repro.engine.planner.calibrate()`` measures and persists
# real ones — and every cost function below resolves them from there when
# the caller does not pin them explicitly.


def _radix_digit_bits(digit_bits: Optional[int]) -> int:
    return digit_bits if digit_bits is not None \
        else _tuning.active().digit_bits


def _radix_tile(tile: Optional[int]) -> int:
    return tile if tile is not None else _tuning.active().radix_tile


def _log2(v: float) -> float:
    return math.log2(max(2.0, v))


def device_sort_cost_ns(method: str, n: int, batch: int = 1, *,
                        run_len: Optional[int] = None,
                        consts: DeviceSortConstants = None,
                        pallas_interpreted: bool = False,
                        key_bits: int = 32,
                        digit_bits: Optional[int] = None,
                        tile: Optional[int] = None) -> float:
    """Estimated ns to sort ``batch`` rows of ``n`` with a software backend.

    ``n`` is priced at its padded (power-of-two / tiled) size, matching what
    each backend actually executes.  ``key_bits`` is the encoded key width
    (keycodec) — only the radix backend's pass count depends on it.
    ``digit_bits`` / ``tile`` default to the active tuning profile's values,
    i.e. exactly what the radix kernel will run with.
    """
    c = consts or _tuning.active().constants
    m = 1 << max(0, (n - 1).bit_length())
    if method == "xla":
        return c.xla * batch * n * _log2(n)
    if method == "bitonic":
        return c.bitonic * batch * m * _log2(m) ** 2
    if method == "pallas":
        pen = c.pallas_interpret_penalty if pallas_interpreted else 1.0
        return pen * c.pallas * batch * m * _log2(m) ** 2
    if method == "radix":
        # O(n·b): ceil(b/digit_bits) digit passes, each touching every
        # element once (histogram + rank + scatter); Pallas kernels, so
        # interpret mode pays the same penalty as the bitonic kernel path
        passes = -(-key_bits // _radix_digit_bits(digit_bits))
        rt = _radix_tile(tile)
        tiled = -(-n // rt) * rt
        pen = c.pallas_interpret_penalty if pallas_interpreted else 1.0
        return pen * c.radix * batch * tiled * passes
    if method == "merge":
        run_len = min(run_len if run_len is not None
                      else _tuning.active().run_len, m)
        tiles = 1 << max(0, (-(-n // run_len) - 1).bit_length())
        padded = tiles * run_len
        gen = c.merge_run * batch * padded * _log2(run_len)
        levels = _log2(tiles) if tiles > 1 else 0.0
        return gen + c.merge_level * batch * padded * levels
    raise ValueError(f"no device cost model for method {method!r}")


def selection_cost_ns(n: int, k: int, key_bits: int = 32, batch: int = 1, *,
                      consts: DeviceSortConstants = None,
                      digit_bits: Optional[int] = None,
                      tile: Optional[int] = None) -> float:
    """Estimated ns for an exact top-k *selection* of ``(batch, n)`` rows —
    the partial-sort operating mode the hardware-sorting survey treats as
    first-class, priced so the planner can weigh it against sort-prefix:

      ceil(b/digit_bits) MSD digit-refinement passes, each one O(n)
      counting work over the (tile-padded) row, plus the O(k log k)
      two-key ordering of the k survivors.

    No interpret penalty: off-TPU the select runs its jnp scatter-add
    histogram (kernels/radix_select.py), not an interpreted Pallas kernel
    — selection is exactly the radix path that stays fast on hosts.
    """
    c = consts or _tuning.active().constants
    passes = -(-key_bits // _radix_digit_bits(digit_bits))
    rt = _radix_tile(tile)
    tiled = -(-n // rt) * rt
    return c.select * batch * tiled * passes + c.xla * batch * k * _log2(k)


def xla_topk_cost_ns(n: int, k: int, batch: int = 1, *,
                     consts: DeviceSortConstants = None) -> float:
    """Estimated ns for the native ``jax.lax.top_k`` lowering on substrates
    where it is a tuned O(n) selection (XLA:CPU): one linear scan plus the
    O(k log k) ordering of the survivors.

    This is the price whose *absence* caused the ROADMAP-flagged ~90x
    auto-dispatch inversion: with the xla candidate priced at the full
    sort-prefix contract, ``auto`` preferred radix-select at n=1M/k=64
    (313ms measured) over the native path (3.4ms).  The k-aware planner
    now asks each backend for its top-k price
    (``SortBackend.topk_cost_ns``) and the xla backend answers with this
    model off-TPU.
    """
    c = consts or _tuning.active().constants
    return c.xla_topk * batch * n + c.xla * batch * k * _log2(k)


def bytes_moved(method: str, n: int, itemsize: int = 4, *,
                key_bits: int = 32, k: int = None,
                run_len: Optional[int] = None,
                digit_bits: Optional[int] = None) -> int:
    """Analytic off-chip bytes one backend moves sorting ``n`` elements —
    the paper's data-movement accounting (Tables I/II count temp-row COPY
    cycles; this counts the software analogue: element reads+writes that
    leave the compute unit's resident tile).

    Comparison sorts move every element once per level; the radix path
    once per digit pass; the VMEM-resident network loads and stores the
    tile exactly once (the in-memory argument); selection's counting
    passes are read-only.  Used by ``benchmarks/emit_bench.py`` to put a
    ``bytes_moved`` column next to every measured ns in BENCH_sort.json.
    """
    if k is not None:
        passes = -(-key_bits // _radix_digit_bits(digit_bits))
        if method == "select":
            return n * itemsize * passes + 2 * k * itemsize
        if method == "xla":            # native scan: one read, k writes
            return n * itemsize + 2 * k * itemsize
        # sort-prefix on any sort backend: full sort + one k-slice read
        return bytes_moved(method, n, itemsize, key_bits=key_bits,
                           run_len=run_len, digit_bits=digit_bits) \
            + k * itemsize
    lvl = _log2(n)
    if method in ("xla", "merge"):
        # merge family: each level reads and writes every element; the
        # engine pays log2(tiles) levels + run generation, ~log2(n) total
        return int(2 * n * itemsize * lvl)
    if method == "bitonic":
        return int(2 * n * itemsize * lvl * lvl)
    if method == "pallas":
        return 2 * n * itemsize        # VMEM-resident: in once, out once
    if method == "radix":
        passes = -(-key_bits // _radix_digit_bits(digit_bits))
        return 2 * n * itemsize * passes
    raise ValueError(f"no bytes-moved model for method {method!r}")


# ---- relational kernels (repro.relational auto-dispatch) ---------------------
#
# Every relational op is priced as (sort backbone) + (O(n) post-pass): the
# survey's framing — group-by/join/dedup are a sorter plus a scan.  The
# post-pass is a handful of elementwise/searchsorted sweeps over the sorted
# column, so its unit price is the measured one-merge-level constant
# (``merge_level``: one O(n) gather-bound pass) times a per-op pass count.
# No new tuning-profile fields: relational pricing reuses the calibrated
# sort constants, so persisted profiles stay schema-stable.

REL_POST_PASSES: Dict[str, float] = {
    "unique": 3.0,     # boundary mask + compaction search + pad
    "group_by": 4.0,   # boundary + compaction + segment reduce (per agg ~1)
    "join": 6.0,       # 2x searchsorted runs + offset scan + pair expansion
    "rle": 3.0,        # boundary + compaction + segment lengths
    "delta": 1.0,      # one adjacent-diff sweep
}

# ops that sort more than one column (join sorts both sides)
REL_SORT_COLUMNS: Dict[str, float] = {"join": 2.0}


def relational_cost_ns(op: str, method: str, n: int, batch: int = 1, *,
                       run_len: Optional[int] = None,
                       key_bits: int = 32,
                       consts: DeviceSortConstants = None,
                       pallas_interpreted: bool = False) -> float:
    """Estimated ns for relational ``op`` over an ``n``-element column with
    its sort backbone on ``method``.

    The planner's ``choose_relational`` prices every auto candidate with
    this — substituting the forced-stable merge pipeline for non-stable
    backends on order-sensitive ops (join pair order, group-by arrival
    ranks) BEFORE calling, since that is what the engine actually executes.
    The sketches are priced too (quantile at its selection contract,
    histogram at one binary-search sweep) so bench tooling can put a
    predicted column next to every measured row, but they take no backend
    override — there is nothing to dispatch.
    """
    c = consts or _tuning.active().constants
    if op == "quantile":
        # bottom-k selection at the median contract (k grows with the
        # highest requested fraction; n/2 is the representative price)
        return selection_cost_ns(n, max(1, n // 2), key_bits, batch,
                                 consts=c)
    if op == "histogram":
        # one searchsorted sweep over the edges + a bincount scatter
        return c.xla * batch * n * _log2(n)
    if op not in REL_POST_PASSES:
        raise ValueError(f"no relational cost model for op {op!r}")
    sort_ns = device_sort_cost_ns(method, n, batch, run_len=run_len,
                                  consts=c, key_bits=key_bits,
                                  pallas_interpreted=pallas_interpreted)
    post = c.merge_level * batch * n * REL_POST_PASSES[op]
    return REL_SORT_COLUMNS.get(op, 1.0) * sort_ns + post


def spill_sort_cost_ns(n: int, batch: int = 1, itemsize: int = 4, *,
                       chunk_bytes: Optional[int] = None,
                       key_bits: int = 32,
                       overlap: bool = True,
                       consts: DeviceSortConstants = None) -> float:
    """Estimated ns for the out-of-core spill tier (``repro.engine.spill``)
    over ``batch`` rows of ``n`` elements.

    Three terms, mirroring the paper's accounting that off-chip movement —
    not compute — dominates once data outgrows the compute unit's memory:

      chunk sorts   ceil(total/chunk) device sorts at the chunk size,
                    priced at the registry's comparison-sort contract
      link transfer every element crosses the host<->device link four
                    times (chunk H2D, run D2H, merge-block H2D, merged
                    D2H) at ``pcie_per_byte``; with double buffering the
                    *spill phase's* half overlaps the chunk sorts, so
                    the overlapped pipeline pays max(sorts, spill-xfer)
                    instead of their sum
      host merge    ceil(log2(chunks)) effective fan-in levels of host
                    cursor partitioning + device block merges at
                    ``host_merge_level`` per element

    ``chunk_bytes`` defaults to the active profile's
    ``spill_threshold_bytes`` — the same knob the planner routes on.
    """
    c = consts or _tuning.active().constants
    cb = chunk_bytes if chunk_bytes is not None \
        else _tuning.active().spill_threshold_bytes
    chunk = max(1, cb // max(1, itemsize))
    total = n * batch
    n_chunks = max(1, -(-total // chunk))
    per_chunk = device_sort_cost_ns("xla", min(chunk, total), consts=c,
                                    key_bits=key_bits)
    sort_ns = n_chunks * per_chunk
    spill_xfer = 2.0 * total * itemsize * c.pcie_per_byte   # H2D + D2H
    merge_xfer = 2.0 * total * itemsize * c.pcie_per_byte   # blocks in/out
    pipeline = max(sort_ns, spill_xfer) if overlap else sort_ns + spill_xfer
    levels = _log2(n_chunks) if n_chunks > 1 else 0.0
    merge_ns = c.host_merge_level * total * levels
    return pipeline + merge_xfer + merge_ns


def collective_cost_ns(n_dev: int, m: int, itemsize: int,
                       consts: DeviceSortConstants = None) -> float:
    """Estimated ns for ONE collective round in which every device
    exchanges ``n_dev`` shards of ``m`` elements.

    ``n_dev=1`` prices a neighbour ppermute (odd-even transposition pays D
    of these); ``n_dev=D`` prices a capacity-padded all-to-all (sample-sort
    pays two: the bucket exchange and the rank rebalance).  This is the
    cluster-scale Eq. 3-4 term: temp-row operand movement priced per
    exchange, with the strategy choice reducing to *how many exchanges*.
    """
    c = consts or _tuning.active().constants
    return c.collective_alpha + c.collective_per_byte * n_dev * m * itemsize


def distributed_sort_cost_ns(strategy: str, n: int, n_dev: int,
                             itemsize: int = 4, *,
                             consts: DeviceSortConstants = None) -> float:
    """Estimated ns to globally sort ``n`` elements over ``n_dev`` devices.

    Both strategies pay the same local shard sort; they differ in movement
    and merge structure:

      oddeven   D rounds x (one shard ppermute + a 2m bitonic merge box)
      sample    2 all-to-alls + one merge-path tree over the received runs

    so odd-even wins at small (n, D) on collective launch count and sample
    wins once the per-round merge work dominates — the planner picks the
    winner per workload (``planner.choose_distributed``).
    """
    c = consts or _tuning.active().constants
    m = -(-n // n_dev)
    local = c.xla * m * _log2(m)
    if strategy == "oddeven":
        round_merge = c.bitonic * (2 * m) * _log2(2 * m)
        return local + n_dev * (collective_cost_ns(1, m, itemsize, c)
                                + round_merge)
    if strategy == "sample":
        # r*m·log r aggregates the capacity-padded exchange staging and
        # merge tree over received runs; + m covers the rank-rebalance
        # shard materialisation — fitted so the modeled crossover matches
        # the measured one (README §Distributed sort)
        r = 1 << max(0, (n_dev - 1).bit_length())
        merge = c.merge_level * ((r * m) * (_log2(r) if r > 1 else 0.0) + m)
        return local + 2 * collective_cost_ns(n_dev, m, itemsize, c) + merge
    raise ValueError(
        f"no distributed cost model for strategy {strategy!r}")


# ---- report helpers ----------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PaperClaims:
    """Every quantitative claim we validate, with model + paper values."""
    rows: tuple

    def all_pass(self) -> bool:
        return all(abs(m - p) <= tol for (_, m, p, tol) in self.rows)


def validate_claims() -> PaperClaims:
    rows = (
        ("Eq1 N_CAS(8)", network.n_cas_blocks(8), 24, 0),
        ("Eq2 N_stages(8)", network.n_stages(8), 6, 0),
        ("Eq3 temp rows(8)", network.n_temp_rows(8), 2, 0),
        ("Eq4 movement cycles per exchange(8)", network.movement_cycles(8), 6, 0),
        ("CAS cycles (W=4)", cas_cycles(4), 28, 0),
        ("reconstructed CAS program cycles (W=4)",
         cas.cached_program(4).total_cycles, 28, 0),
        ("total movement cycles (N=8)", network.total_extra_cycles(8), 24, 0),
        ("sort cycles (N=8, W=4)", sort_cycles(8), 192, 0),
        ("Table I NOR total (N=8)", stage_op_totals(8)["NOR"], 84, 0),
        ("Table I NOT total (N=8)", stage_op_totals(8)["NOT"], 48, 0),
        ("Table I AND total (N=8)", stage_op_totals(8)["AND"], 18, 0),
        ("Table I COPY total (N=8)", stage_op_totals(8)["COPY"], 42, 0),
        ("Table II latency ns", sort_latency_ns(8), 105.6, 1e-9),
        ("Table II throughput GOPS", throughput_gops(8), 1.8, 0.02),
        ("Table II frequency GHz", OPERATING_FREQ_GHZ, 1.81, 0.01),
        ("array geometry rows (W=4)", cas.cached_program(4).n_rows, 22, 0),
        ("Fig8a MemSort cycle ratio", memsort_cycles(8) / sort_cycles(8), 1.45, 1e-12),
        ("Fig8b MemSort latency ratio",
         memsort_latency_ns(8) / sort_latency_ns(8), 3.4, 1e-12),
        ("off-memory latency ratio",
         off_memory_latency_ns(8) / sort_latency_ns(8), 5.0, 1e-12),
    )
    return PaperClaims(rows=rows)
