"""Sharding policy: logical placement rules -> NamedSharding constraints.

One object carries every distribution decision (DESIGN.md §4):

  * mesh axes: optional 'pod' (pure DP, crosses DCN), 'data' (FSDP batch +
    parameter shard), 'model' (TP/EP).
  * parameters: 2-D sharded per the specs each module emits (FSDP on 'data',
    TP on 'model'); the 'pod' axis never shards parameters.
  * activations: batch on (pod, data); attention heads on 'model' when the
    head count divides, else head_dim, else replicated — this fallback chain
    is what lets whisper-tiny (6 heads) and gemma (8 heads / MQA) compile on
    a 16-way TP axis.
  * KV cache: kv heads optionally *repeated* up to the TP degree so the cache
    shards instead of replicating ("repeat-to-TP", factor tp/n_kv).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class ShardingPolicy:
    mesh: Optional[Mesh]
    dp_axes: Tuple[str, ...] = ("data",)
    tp_axis: str = "model"
    seq_shard: bool = False       # sequence parallelism on the residual stream
    cp_layout: bool = False       # context-parallel prefill: activations
    # sequence-sharded over 'model' end-to-end; flash q-blocks stay local
    # against gathered K/V (EXPERIMENTS.md §Perf iC.3)
    serve_layout: bool = False    # DP-heavy inference layout: layer weights
    # FSDP-sharded over (data x model), activations replicated over 'model',
    # KV cache sequence-sharded — removes the per-layer TP all-reduces that
    # dominate the prefill roofline (EXPERIMENTS.md §Perf iC.2)

    def __post_init__(self):
        if self.mesh is not None:
            from repro.core.topology import auto_mesh
            object.__setattr__(self, "mesh", auto_mesh(self.mesh))

    # ------------------------------------------------------------- helpers
    @property
    def tp_size(self) -> int:
        if self.mesh is None or self.tp_axis not in self.mesh.shape:
            return 1
        return self.mesh.shape[self.tp_axis]

    @property
    def dp_size(self) -> int:
        if self.mesh is None:
            return 1
        n = 1
        for a in self.dp_axes:
            n *= self.mesh.shape.get(a, 1)
        return n

    def _constrain(self, x, spec: P):
        if self.mesh is None:
            return x
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def kv_repeat(self, n_kv: int, n_heads: int) -> int:
        """Repeat factor R/n_kv for the stored KV heads (repeat-to-TP)."""
        if self.serve_layout:
            return 1              # cache shards on sequence, not heads
        tp = self.tp_size
        if (n_kv < tp <= n_heads and n_heads % tp == 0 and tp % n_kv == 0):
            return tp // n_kv
        return 1

    def _heads_spec(self, n_heads: int, head_dim: int) -> P:
        """Attention ACTIVATIONS (B,S,N,H): shard heads if they divide, else
        replicate — sharding head_dim here would split RoPE's rotation pairs
        and forces involuntary resharding around the merge-heads reshape."""
        dp = self.dp_axes
        tp = self.tp_size
        if not self.serve_layout and tp > 1 and n_heads % tp == 0:
            return P(dp, None, self.tp_axis, None)
        return P(dp, None, None, None)

    def _cache_spec(self, n_heads: int, head_dim: int) -> P:
        """KV-cache STORAGE: persistent and large, so fall back to sharding
        head_dim when the (repeated) kv-head count does not divide TP."""
        dp = self.dp_axes
        tp = self.tp_size
        if tp > 1 and n_heads % tp == 0:
            return P(dp, None, self.tp_axis, None)
        if tp > 1 and head_dim % tp == 0:
            return P(dp, None, None, self.tp_axis)
        return P(dp, None, None, None)

    # ------------------------------------------------------------ act hooks
    def shard_activations(self, x):
        """Residual stream (B, S, D): batch over DP axes; with seq_shard the
        sequence dim also shards over the TP axis (Megatron-style SP — the
        norms are pointwise over D, attention/FFN gather what they need).
        This divides the remat-saved per-layer residuals by tp_size."""
        if (self.seq_shard and self.tp_size > 1 and x.ndim == 3
                and x.shape[1] % self.tp_size == 0 and x.shape[1] > 1):
            return self._constrain(x, P(self.dp_axes, self.tp_axis, None))
        return self._constrain(x, P(self.dp_axes, None, None))

    def sp_gather(self, x):
        """Megatron-SP all-gather point: norm outputs enter the matmuls with
        the FULL sequence (replicated over TP).  Placing the constraint here
        makes GSPMD gather the (B,S,D) activations (~300 MB) instead of the
        fp32-upcast weights (5.4 GB on nemotron — measured) and positions
        the seq all-gather exactly once per block input."""
        if self.seq_shard and self.tp_size > 1 and x.ndim == 3:
            return self._constrain(x, P(self.dp_axes, None, None))
        return x

    def sp_scatter(self, y):
        """Megatron-SP reduce-scatter point: block outputs return to the
        seq-sharded layout immediately, so the TP partial-sum lowers to a
        reduce-scatter instead of a full all-reduce (16x less wire)."""
        if (self.seq_shard and self.tp_size > 1 and y.ndim == 3
                and y.shape[1] % self.tp_size == 0 and y.shape[1] > 1):
            return self._constrain(y, P(self.dp_axes, self.tp_axis, None))
        return y

    def shard_logits(self, x):
        """(B, S, V): vocab over the TP axis (the unembedding is
        model-sharded, so this keeps logits where they are produced)."""
        if self.tp_size > 1 and x.shape[-1] % self.tp_size == 0:
            return self._constrain(x, P(self.dp_axes, None, self.tp_axis))
        return self._constrain(x, P(self.dp_axes, None, None))

    def shard_heads(self, x):
        """(B, S, N, H) attention activations."""
        return self._constrain(x, self._heads_spec(x.shape[2], x.shape[3]))

    def shard_cache(self, x):
        return self._constrain(x, self._cache_spec(x.shape[2], x.shape[3]))

    def shard_scores(self, x):
        """Attention scores (B, R, G, S_q, S_k) fp32: pin batch to DP and the
        kv-head axis (leading head factor, blocked grouping) to TP.  Without
        this constraint GSPMD is free to pick a sequence sharding for the
        backward score gradients and then all-gathers the full-batch fp32
        tensor (measured: 12.9 GB/device on nemotron-340b train)."""
        tp = self.tp_size
        r = x.shape[1]
        if tp > 1 and r % tp == 0:
            return self._constrain(x, P(self.dp_axes, self.tp_axis, None,
                                        None, None))
        return self._constrain(x, P(self.dp_axes, None, None, None, None))

    def batch_spec(self, ndim: int = 2) -> P:
        return P(self.dp_axes, *([None] * (ndim - 1)))

    def replicated(self) -> P:
        return P()

    def _sanitize(self, spec: P, shape) -> P:
        out = []
        for i, entry in enumerate(tuple(spec)):
            if entry is None or i >= len(shape):
                out.append(None)
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            axes = tuple(a for a in axes if a in self.mesh.shape)
            size = 1
            for a in axes:
                size *= self.mesh.shape[a]
            ok = axes and shape[i] % size == 0
            out.append((axes if len(axes) > 1 else axes[0]) if ok else None)
        return P(*out)

    def run_sharded_flash(self, q, k, v, *, causal: bool = True,
                          window: int = 0):
        if self.cp_layout and self.mesh is not None:
            return self._run_cp_flash(q, k, v, causal=causal, window=window)
        """Flash attention under a full-manual shard_map: each device runs
        the Pallas kernel on its local (batch, head) shard — GSPMD never
        sees the kernel, so it cannot replicate its inputs.  Forward-only
        (prefill / serving)."""
        from repro.kernels.flash_attention import flash_attention
        if self.mesh is None:
            return flash_attention(q, k, v, causal=causal, window=window)
        qspec = self._sanitize(self._heads_spec(q.shape[2], q.shape[3]),
                               q.shape)
        kspec = self._sanitize(self._heads_spec(k.shape[2], k.shape[3]),
                               k.shape)
        # heads must shard consistently: if q shards on heads but k cannot
        # (r < tp), fall back to replicated heads for both
        if qspec[2] != kspec[2]:
            qspec = self._sanitize(P(self.dp_axes, None, None, None), q.shape)
            kspec = self._sanitize(P(self.dp_axes, None, None, None), k.shape)
        fn = jax.shard_map(
            lambda a, b, c: flash_attention(a, b, c, causal=causal,
                                            window=window),
            mesh=self.mesh, in_specs=(qspec, kspec, kspec),
            out_specs=qspec, check_vma=False)
        return fn(q, k, v)

    def _run_cp_flash(self, q, k, v, *, causal: bool, window: int):
        """Context-parallel flash: q stays SEQUENCE-sharded over the TP
        axis (each shard owns a contiguous q block, passing its global
        origin to the kernel's causal mask); K/V are replicated.  Balances
        attention flops across the model axis without head sharding."""
        from repro.kernels.flash_attention import flash_attention
        dp, tp = self.dp_axes, self.tp_axis
        local_s = q.shape[1] // self.tp_size

        def inner(a, b_, c):
            off = jax.lax.axis_index(tp) * local_s
            return flash_attention(a, b_, c, causal=causal, window=window,
                                   q_offset=off)

        qspec = self._sanitize(P(dp, tp, None, None), q.shape)
        kspec = self._sanitize(P(dp, None, None, None), k.shape)
        fn = jax.shard_map(inner, mesh=self.mesh,
                           in_specs=(qspec, kspec, kspec),
                           out_specs=qspec, check_vma=False)
        return fn(q, k, v)

    # ----------------------------------------------------- param spec tools
    def serve_param_specs(self, specs_tree, keep_data: bool = False):
        """Transform per-layer weight specs for the DP-heavy serve layout:
        'model' is removed and 'data' becomes ('data','model') — every layer
        weight is FSDP-sharded across ALL chips and streamed (one gather per
        layer), so no matmul produces TP partial sums.  Embedding/unembed
        specs (which carry 'model' on the vocab dim by design) are preserved
        by the caller passing only the layer subtrees."""
        def tx(spec):
            if not isinstance(spec, P):
                return spec
            out = []
            for entry in tuple(spec):
                if entry is None:
                    out.append(None)
                elif entry == "data" or entry == ("data",):
                    out.append("data" if keep_data else ("data", "model"))
                elif entry == "model":
                    out.append(None)
                elif isinstance(entry, tuple):
                    out.append(entry)   # already combined
                else:
                    out.append(entry)
            return P(*out)

        return jax.tree.map(tx, specs_tree,
                            is_leaf=lambda x: isinstance(x, P))

    def param_sharding(self, specs_tree):
        """Pytree of PartitionSpec -> pytree of NamedSharding."""
        if self.mesh is None:
            return None
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), specs_tree,
            is_leaf=lambda x: isinstance(x, P))


NO_SHARDING = ShardingPolicy(mesh=None, dp_axes=())
