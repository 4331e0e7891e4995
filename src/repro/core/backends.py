"""The built-in SortBackend implementations.

Each backend is a thin adapter from the registry's rows-form contract
(``(rows, n)``, last axis) onto an existing engine: the jnp/XLA reference,
the word-parallel bitonic network, the in-VMEM Pallas kernel, the
cycle-accurate bit-serial simulator, the out-of-core run/merge hierarchy,
and the LSD radix kernels.  Kernel modules are imported lazily inside the
methods so importing the registry stays cheap and cycle-free.

Capability declarations here are load-bearing: ``repro.engine.planner``
derives *all* auto-dispatch eligibility from them (no per-backend rules in
the planner), and tests/test_sortspec.py sweeps every claim for truth.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import keycodec as _keycodec
from repro.core.sortspec import (Capabilities, SortBackend, next_pow2,
                                 register_backend)

# whole-array network caps: beyond these the power-of-two padded row stops
# being a reasonable VMEM-resident tile and the hierarchy should take over
MAX_BITONIC_N = 1 << 14
MAX_PALLAS_N = 1 << 16

# dtypes every comparison backend's min/max handles (NaN-free floats assumed)
COMPARABLE_DTYPES = frozenset({
    "float32", "bfloat16", "float16", "int32", "uint32",
    "int16", "uint16", "int8", "uint8"})

_INT_DTYPES = frozenset({"int8", "int16", "int32",
                         "uint8", "uint16", "uint32"})


def _gather_kv(keys, values, order):
    """(sorted keys, permuted payload) from an argsort permutation.

    The bitonic/pallas kv networks pad with (sentinel key, position ``n``)
    pairs, which only sort *after* every genuine element when the payload is
    an index array — an arbitrary user payload can tie or exceed the pad
    marker and be displaced by it.  So the kv front doors of those backends
    sort a (key, index) composite and gather both sides instead.
    """
    return (jnp.take_along_axis(keys, order, axis=-1),
            jnp.take_along_axis(values, order, axis=-1))


# ---------------------------------------------------------------------------
# xla — the "off-memory" reference point
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames="descending")
def _xla_sort_kv(keys, values, descending):
    """One stable two-operand sort that carries the payload: the
    permutation of ``jnp.argsort(keys, stable=True)`` with no gather.
    Descending reverses both rows around the ascending sort, as
    ``jnp.argsort`` does, so ties keep ascending input order."""
    if descending:
        keys, values = jnp.flip(keys, -1), jnp.flip(values, -1)
    keys, values = jax.lax.sort((keys, values), dimension=-1, num_keys=1,
                                is_stable=True)
    if descending:
        keys, values = jnp.flip(keys, -1), jnp.flip(values, -1)
    return keys, values


@register_backend
class XlaBackend(SortBackend):
    """jnp.sort / lax.top_k with the repo's grad-safe VJP and the unified
    tie convention (ties keep ascending index order in both directions)."""
    name = "xla"
    capabilities = Capabilities(dtypes=None, stable=True, substrate="host")

    def sort(self, rows, *, descending=False, plan=None, interpret=None):
        from repro.core.sort_api import _xla_sort
        return _xla_sort(rows, -1, descending)

    def sort_kv(self, keys, values, *, descending=False, plan=None,
                interpret=None):
        return _xla_sort_kv(keys, values, descending)

    def argsort(self, rows, *, descending=False, plan=None, interpret=None):
        # jnp's descending comparator == the flip-remap stable form: ties
        # keep ascending index order in BOTH directions
        return jnp.argsort(rows, axis=-1, stable=True, descending=descending)

    def topk(self, rows, k, *, plan=None, interpret=None):
        return jax.lax.top_k(rows, k)

    def topk_cost_ns(self, n, k, batch, dtype, *, run_len, consts=None,
                     interpreted=False):
        """Off-TPU ``lax.top_k`` lowers to XLA:CPU's tuned O(n) native
        selection — price it as one (the ROADMAP-flagged ~90x inversion
        was exactly this candidate priced at the sort-prefix contract).
        On TPU the lowering is sort-based, so the sort-prefix default
        stays the honest price there."""
        from repro.core import cost_model
        if jax.default_backend() == "tpu":
            return super().topk_cost_ns(n, k, batch, dtype, run_len=run_len,
                                        consts=consts,
                                        interpreted=interpreted)
        return cost_model.xla_topk_cost_ns(n, k, batch, consts=consts)


# ---------------------------------------------------------------------------
# bitonic — the paper's network, word-parallel in pure jnp
# ---------------------------------------------------------------------------

@register_backend
class BitonicBackend(SortBackend):
    name = "bitonic"
    capabilities = Capabilities(dtypes=COMPARABLE_DTYPES, stable=False,
                                max_n=MAX_BITONIC_N, substrate="host")

    def sort(self, rows, *, descending=False, plan=None, interpret=None):
        from repro.core.sort_api import bitonic_sort
        return bitonic_sort(rows, axis=-1, descending=descending)

    def sort_kv(self, keys, values, *, descending=False, plan=None,
                interpret=None):
        return _gather_kv(keys, values,
                          self.argsort(keys, descending=descending))

    def argsort(self, rows, *, descending=False, plan=None, interpret=None):
        from repro.core.sort_api import bitonic_sort
        idx = jnp.broadcast_to(
            jnp.arange(rows.shape[-1], dtype=jnp.int32), rows.shape)
        _, order = bitonic_sort(rows, axis=-1, descending=descending,
                                values=idx)
        return order


# ---------------------------------------------------------------------------
# pallas — the whole network on VMEM-resident tiles
# ---------------------------------------------------------------------------

@register_backend
class PallasBackend(SortBackend):
    name = "pallas"
    capabilities = Capabilities(dtypes=COMPARABLE_DTYPES, stable=False,
                                max_n=MAX_PALLAS_N, substrate="vmem")

    def sort(self, rows, *, descending=False, plan=None, interpret=None):
        from repro.kernels import ops as kops
        return kops.bitonic_sort(rows, -1, descending, interpret)

    def argsort(self, rows, *, descending=False, plan=None, interpret=None):
        from repro.kernels import ops as kops
        return kops.bitonic_argsort(rows, -1, descending, interpret)

    def sort_kv(self, keys, values, *, descending=False, plan=None,
                interpret=None):
        return _gather_kv(keys, values,
                          self.argsort(keys, descending=descending,
                                       interpret=interpret))

    def topk(self, rows, k, *, plan=None, interpret=None):
        from repro.kernels import ops as kops
        # positional: custom_vjp entry points don't take keyword args
        return kops.bitonic_topk(rows, k, kops._TOPK_CHUNK, interpret)


# ---------------------------------------------------------------------------
# imc — the faithful bit-serial simulation
# ---------------------------------------------------------------------------

@register_backend
class ImcBackend(SortBackend):
    """The 28-cycle gate program on the simulated 6T SRAM array.  Validation
    and benchmarking only (never auto-dispatched); keys go through the
    order-preserving codec so signed ints sort correctly."""
    name = "imc"
    capabilities = Capabilities(dtypes=_INT_DTYPES, stable=False,
                                supports_kv=False, supports_topk=False,
                                supports_segments=False, auto_dispatch=False,
                                substrate="sram")

    def sort(self, rows, *, descending=False, plan=None, interpret=None):
        from repro.core import keycodec, sorter
        self.check_dtype(rows.dtype)
        enc = keycodec.encode(rows)
        res = sorter.sort_in_memory(enc, width=keycodec.key_bits(rows.dtype))
        out = keycodec.decode(
            res.values.astype(keycodec.key_dtype(rows.dtype)), rows.dtype)
        return jnp.flip(out, axis=-1) if descending else out

    def argsort(self, rows, *, descending=False, plan=None, interpret=None):
        """Argsort on the bit-serial sorter via the shared
        ``keycodec.argsort_composite`` packing: unique composites give the
        (unstable) network the engine's tie convention — ties keep
        ascending index order in both directions."""
        from repro.core import keycodec, sorter
        self.check_dtype(rows.dtype)
        comp, idx_bits = keycodec.argsort_composite(rows,
                                                    descending=descending)
        # the CAS gate program is built for power-of-two word widths
        width = next_pow2(keycodec.key_bits(rows.dtype) + idx_bits)
        res = sorter.sort_in_memory(comp, width=width)
        return (res.values & ((1 << idx_bits) - 1)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# merge — the hierarchical out-of-core engine
# ---------------------------------------------------------------------------

@register_backend
class MergeBackend(SortBackend):
    """Tiled run generation + merge-path merge tree (repro.engine)."""
    name = "merge"
    capabilities = Capabilities(dtypes=COMPARABLE_DTYPES, stable=False,
                                substrate="hierarchy")

    def eligible(self, n, dtype, run_len=None):
        # a single run degenerates to "sort one tile and merge nothing"
        if run_len is not None and n <= run_len:
            return False
        return super().eligible(n, dtype, run_len)

    def _plan(self, rows, plan, run_len=None):
        if plan is not None:
            return plan
        from repro.engine import planner
        return planner.choose_cached(rows.shape[-1], rows.shape[0],
                                     rows.dtype, requested="merge",
                                     run_len=run_len)

    def sort(self, rows, *, descending=False, plan=None, interpret=None):
        from repro import engine
        return engine.merge_sort_rows(rows, descending=descending,
                                      plan=self._plan(rows, plan),
                                      interpret=interpret)

    def sort_kv(self, keys, values, *, descending=False, plan=None,
                interpret=None):
        from repro import engine
        return engine.merge_sort_rows_kv(keys, values, descending=descending,
                                         plan=self._plan(keys, plan),
                                         interpret=interpret)


# ---------------------------------------------------------------------------
# radix — digit-serial LSD radix sort over encoded keys
# ---------------------------------------------------------------------------

@register_backend
class RadixBackend(SortBackend):
    """Stable LSD radix sort (kernels/radix_sort.py) through the
    order-preserving key codec; ``descending`` complements the encoded key,
    so ties keep ascending index order in both directions."""
    name = "radix"
    capabilities = Capabilities(dtypes=frozenset(_keycodec.SUPPORTED),
                                stable=True, substrate="vmem")

    def sort(self, rows, *, descending=False, plan=None, interpret=None):
        from repro.core import keycodec
        from repro.kernels import radix_sort as _rs
        from repro.obs import trace as _obs
        self.check_dtype(rows.dtype)
        n = rows.shape[-1]
        passes, tiles = _rs.pass_tile_counts(n, rows.dtype)
        with _obs.trace("radix.sort", n=n, passes=passes, tiles=tiles):
            enc = keycodec.encode(rows, descending=descending)
            out = _rs.sort_blocks(enc, interpret=interpret)
            return keycodec.decode(out, rows.dtype, descending=descending)

    def sort_kv(self, keys, values, *, descending=False, plan=None,
                interpret=None):
        from repro.core import keycodec
        from repro.kernels import radix_sort as _rs
        from repro.obs import trace as _obs
        self.check_dtype(keys.dtype)
        n = keys.shape[-1]
        passes, tiles = _rs.pass_tile_counts(n, keys.dtype)
        with _obs.trace("radix.sort_kv", n=n, passes=passes, tiles=tiles):
            enc = keycodec.encode(keys, descending=descending)
            sk, sv = _rs.sort_kv_blocks(enc, values, interpret=interpret)
            return keycodec.decode(sk, keys.dtype,
                                   descending=descending), sv


# ---------------------------------------------------------------------------
# select — MSD radix-select, the O(n) partial-sort mode
# ---------------------------------------------------------------------------

@register_backend
class SelectBackend(SortBackend):
    """MSD radix-select (kernels/radix_select.py): top-k via keycodec
    digit histograms + threshold refinement — O(n·b/8) counting passes,
    never a sort.  Selection-only (``supports_sort=False``): plain sort
    specs are rejected at the spec layer; the planner prices its top-k
    specs with ``cost_model.selection_cost_ns`` and auto-dispatches it
    once ``k ≪ n`` makes selection cheaper than sort-prefix.  Exact-k
    with ``jax.lax.top_k``'s tie rule (ties keep ascending index)."""
    name = "select"
    capabilities = Capabilities(dtypes=frozenset(_keycodec.SUPPORTED),
                                stable=False, supports_kv=False,
                                supports_segments=False, supports_sort=False,
                                selection=True, substrate="vmem")

    def topk(self, rows, k, *, plan=None, interpret=None):
        from repro.kernels import radix_select as _sel
        from repro.obs import trace as _obs
        self.check_dtype(rows.dtype)
        n = rows.shape[-1]
        passes, tiles = _sel.pass_tile_counts(n, rows.dtype)
        with _obs.trace("select.topk", n=n, k=k, passes=passes,
                        tiles=tiles):
            return _sel.select_topk(rows, k, interpret=interpret)


# ---------------------------------------------------------------------------
# distributed — mesh-global sorting (sample-sort + odd-even fallback)
# ---------------------------------------------------------------------------

@register_backend
class DistributedBackend(SortBackend):
    """Mesh-global sorting behind the registry: the single-round
    sample-sort (engine/samplesort.py, over one mesh axis or several)
    with odd-even transposition as the small-(n, D) single-axis
    fallback, strategy priced by ``planner.choose_distributed``.

    The natural entry is a spec carrying mesh fields —
    ``SortSpec(mesh=..., axis_name=...)`` through ``repro.sort`` — which
    lands on :meth:`sort_mesh`.  The rows-form methods keep the backend an
    honest registry citizen (capability sweeps, single-host use): each row
    is sorted globally over whatever device mesh this host offers, which
    on one device degenerates to the local registered-backend sort.
    Never auto-dispatched by the single-device planner; the mesh path has
    its own cost model.
    """
    name = "distributed"
    capabilities = Capabilities(dtypes=frozenset(_keycodec.SUPPORTED),
                                stable=False, supports_segments=False,
                                selection=True, auto_dispatch=False,
                                substrate="mesh")

    @staticmethod
    def _host_mesh():
        from repro.launch.mesh import make_host_mesh
        return make_host_mesh()

    # -- mesh execution (what SortSpec.mesh routes to) ----------------------
    def sort_mesh(self, x, mesh, axis_name, *, values=None, descending=False,
                  local_method=None, interpret=None):
        from repro.core import distributed_sort as _ds
        return _ds.distributed_sort(x, mesh, axis_name,
                                    local_method=local_method,
                                    strategy="auto", descending=descending,
                                    values=values, interpret=interpret)

    def topk_mesh(self, x, k, mesh, axis_name, *, interpret=None):
        """Mesh-global top-k: local radix-select per shard, ONE candidate
        all-gather of D·min(k, m) (key, index) pairs, tiny lexicographic
        merge — no full-array sort ever runs."""
        from repro.core import distributed_sort as _ds
        return _ds.distributed_topk(x, k, mesh, axis_name,
                                    interpret=interpret)

    # -- rows form ----------------------------------------------------------
    def sort(self, rows, *, descending=False, plan=None, interpret=None):
        from repro.engine import samplesort
        self.check_dtype(rows.dtype)
        mesh = self._host_mesh()
        return jnp.stack([
            samplesort.sample_sort(r, mesh, "data", descending=descending,
                                   interpret=interpret) for r in rows])

    def sort_kv(self, keys, values, *, descending=False, plan=None,
                interpret=None):
        from repro.engine import samplesort
        self.check_dtype(keys.dtype)
        mesh = self._host_mesh()
        outs = [samplesort.sample_sort(k, mesh, "data", values=v,
                                       descending=descending,
                                       interpret=interpret)
                for k, v in zip(keys, values)]
        return (jnp.stack([k for k, _ in outs]),
                jnp.stack([v for _, v in outs]))

    def topk(self, rows, k, *, plan=None, interpret=None):
        """Rows form of the mesh top-k: each row runs the candidate path
        over whatever device mesh this host offers (on one device it
        degenerates to the local radix-select)."""
        from repro.engine import samplesort
        self.check_dtype(rows.dtype)
        mesh = self._host_mesh()
        outs = [samplesort.sample_topk(r, k, mesh, "data",
                                       interpret=interpret) for r in rows]
        return (jnp.stack([v for v, _ in outs]),
                jnp.stack([i for _, i in outs]))

    def argsort(self, rows, *, descending=False, plan=None, interpret=None):
        """Engine tie convention (ties keep ascending index order) on an
        unstable distributed sort, via the shared
        ``keycodec.argsort_composite`` packing (same width limit as the
        imc composite path)."""
        from repro.engine import samplesort
        self.check_dtype(rows.dtype)
        comp, idx_bits = _keycodec.argsort_composite(rows,
                                                     descending=descending)
        mesh = self._host_mesh()
        out = jnp.stack([samplesort.sample_sort(c, mesh, "data",
                                                interpret=interpret)
                         for c in comp])
        return (out & ((1 << idx_bits) - 1)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# spill — out-of-core: chunked device sorts + host-resident k-way merge
# ---------------------------------------------------------------------------

@register_backend
class SpillBackend(SortBackend):
    """Out-of-core spill-to-host tier (``repro.engine.spill``): the input
    is cut into ``spill_threshold_bytes`` chunks, each chunk sorted on
    device through the registry (``method="auto"``), sorted runs streamed
    to host with double-buffered transfers, and a k-way merge-path
    combines the host-resident runs block by block.

    Never auto-*priced* (``auto_dispatch=False``): the planner routes to
    it by *feasibility* — any workload whose key bytes exceed the active
    profile's ``spill_threshold_bytes`` spills, everything below never
    does — rather than by cost comparison against backends that could not
    hold the array anyway.  Host-driven and eager-only: under an outer
    ``jit`` the engine falls back to the on-device merge pipeline.

    The kv path is always stable (stable chunk sorts + run-index tie
    breaks in both merge stages), so the capability claim is honest for
    the sweep tests.  No top-k/segmented paths (a dataset-scale top-k
    wants per-chunk selection + candidate merge — ROADMAP follow-through,
    not a sort-everything fallback).
    """
    name = "spill"
    # numpy owns the host half (searchsorted cursors, run storage);
    # bfloat16 — which numpy's comparators don't know — rides the
    # pipeline as its uint16 keycodec encoding (spill._bf16_encode), so
    # the full COMPARABLE_DTYPES set is honest
    capabilities = Capabilities(
        dtypes=frozenset({"float32", "float16", "bfloat16", "int32",
                          "uint32", "int16", "uint16", "int8", "uint8"}),
        stable=True, supports_kv=True, supports_topk=False,
        supports_segments=False, auto_dispatch=False, substrate="host")

    def sort(self, rows, *, descending=False, plan=None, interpret=None):
        from repro.engine import spill
        self.check_dtype(rows.dtype)
        return spill.sort_rows(rows, descending=descending,
                               interpret=interpret)

    def sort_kv(self, keys, values, *, descending=False, plan=None,
                interpret=None):
        from repro.engine import spill
        self.check_dtype(keys.dtype)
        return spill.sort_rows_kv(keys, values, descending=descending,
                                  interpret=interpret)

    def argsort(self, rows, *, descending=False, plan=None, interpret=None):
        from repro.engine import spill
        self.check_dtype(rows.dtype)
        return spill.argsort_rows(rows, descending=descending,
                                  interpret=interpret)
