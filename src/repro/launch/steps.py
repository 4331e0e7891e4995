"""Step-function builders: train / prefill / serve, with their shardings.

These are the single source of truth for what gets jitted, lowered in the
dry-run, benchmarked, and executed by train.py / serve.py — so the dry-run
compiles EXACTLY the production step.

train_step = grad-accumulation scan over microbatches (fits the 4k x 256
global batch on the big dense configs and overlaps the cross-pod gradient
all-reduce with the next microbatch's compute) + optimizer update + bf16
parameter refresh.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeSpec
from repro.core.topology import auto_mesh, to_auto_mesh
from repro.models.model_zoo import Model
from repro.optim import optimizers as opt_lib
from repro.sharding.partitioning import ShardingPolicy


# ---------------------------------------------------------------------------
# spec utilities
# ---------------------------------------------------------------------------

def batch_specs(model: Model, shape: ShapeSpec, policy: ShardingPolicy):
    """PartitionSpec tree matching model.input_specs(shape)."""
    dp = policy.dp_axes
    specs = {}
    for name, sds in model.input_specs(shape).items():
        if name == "positions":            # (3, B, S)
            specs[name] = P(None, dp, None)
        else:
            specs[name] = P(dp, *([None] * (len(sds.shape) - 1)))
    return specs


def _state_leaf_spec(path_str: str, leaf, policy: ShardingPolicy,
                     tp_ok) -> P:
    dp = policy.dp_axes
    body = "body" in path_str
    nd = leaf.ndim - (1 if body else 0)    # strip stacked-layer axis
    lead = (None,) if body else ()
    if nd == 4:                            # KV cache (B, S, R, H)
        s, r, h = leaf.shape[-3], leaf.shape[-2], leaf.shape[-1]
        tp = policy.tp_size
        if getattr(policy, "serve_layout", False) and tp > 1 \
                and s % tp == 0:
            # DP-heavy serve layout: cache shards on SEQUENCE
            return P(*lead, dp, policy.tp_axis, None, None)
        if tp > 1 and r % tp == 0:
            return P(*lead, dp, None, policy.tp_axis, None)
        if tp > 1 and h % tp == 0:
            return P(*lead, dp, None, None, policy.tp_axis)
        return P(*lead, dp, None, None, None)
    if nd == 0:
        return P()
    return P(*lead, dp, *([None] * (nd - 1)))


def decode_state_specs(state_abstract, policy: ShardingPolicy):
    def spec(path, leaf):
        pstr = jax.tree_util.keystr(path)
        return _state_leaf_spec(pstr, leaf, policy, None)
    return jax.tree_util.tree_map_with_path(spec, state_abstract)


def sanitize_specs(specs, abstract, mesh: Optional[Mesh]):
    """Drop spec entries whose dimension does not divide the mesh axes —
    the safety net that lets odd sizes (vocab 51865, batch 1) compile
    replicated instead of erroring."""
    if mesh is None:
        return specs

    def fix(spec, arr):
        if not isinstance(spec, P):
            return spec
        entries = tuple(spec)
        out = []
        for i, entry in enumerate(entries):
            if entry is None or i >= arr.ndim:
                out.append(None)
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            # drop axes absent from this mesh (host meshes have no 'model')
            axes = tuple(a for a in axes if a in mesh.shape)
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            if not axes or arr.shape[i] % size != 0:
                out.append(None)
            else:
                out.append(axes if len(axes) > 1 else axes[0])
        return P(*out)

    return jax.tree.map(fix, specs, abstract,
                        is_leaf=lambda x: isinstance(x, P))


def abstract_init(model: Model, key):
    """(abstract params, partition specs) without allocating anything."""
    box = {}

    def params_only(k):
        p, s = model.init(k)
        box["specs"] = s
        return p

    params_abs = jax.eval_shape(params_only, key)
    return params_abs, box["specs"]


def shardings_of(tree_specs, mesh: Optional[Mesh]):
    if mesh is None:
        return None
    mesh = auto_mesh(mesh)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree_specs,
                        is_leaf=lambda x: isinstance(x, P))


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TrainStep:
    fn: Any                    # (params, opt_state, step, batch) -> ...
    params_specs: Any
    opt_specs: Any
    batch_specs: Any

    def jit(self, mesh: Optional[Mesh], donate: bool = True):
        in_sh = (shardings_of(self.params_specs, mesh),
                 shardings_of(self.opt_specs, mesh),
                 NamedSharding(mesh, P()) if mesh else None,
                 shardings_of(self.batch_specs, mesh))
        out_sh = (shardings_of(self.params_specs, mesh),
                  shardings_of(self.opt_specs, mesh),
                  NamedSharding(mesh, P()) if mesh else None)
        kw = dict(donate_argnums=(0, 1)) if donate else {}
        if mesh is None:
            return jax.jit(self.fn, **kw)
        return jax.jit(self.fn, in_shardings=in_sh, out_shardings=out_sh,
                       **kw)


def build_train_step(model: Model, optimizer: opt_lib.Optimizer,
                     policy: ShardingPolicy, shape: ShapeSpec,
                     microbatch: int = 1, accum_dtype=jnp.float32,
                     grad_compressor=None) -> TrainStep:
    def loss_fn(params, mb):
        loss, aux = model.loss(params, mb)
        return loss, aux

    def train_step(params, opt_state, step, batch):
        if policy is not None and policy.mesh is not None:
            # inputs placed on an Explicit-axes mesh join the policy's
            # Auto-typed one (the model's placement rules are constraints)
            batch = jax.tree.map(lambda x: to_auto_mesh(x, policy.mesh),
                                 batch)
        if microbatch > 1:
            # microbatch i takes rows i, i + microbatch, ...: the batch
            # dimension's data sharding stays on the per-microbatch rows
            # (every device works in every microbatch), and the scanned
            # microbatch axis stays unsharded on Explicit-axes meshes
            def split(x):
                if x.ndim >= 2 and x.shape[0] == 3:   # (3,B,S) positions
                    return jnp.moveaxis(
                        x.reshape(3, -1, microbatch, *x.shape[2:]), 2, 0)
                return jnp.moveaxis(
                    x.reshape(-1, microbatch, *x.shape[1:]), 1, 0)

            mbs = jax.tree.map(split, batch)

            def accum(carry, mb):
                gsum, lsum = carry
                (loss, aux), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mb)
                gsum = jax.tree.map(
                    lambda a, g: a + g.astype(accum_dtype), gsum, grads)
                return (gsum, lsum + loss), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, accum_dtype), params)
            (gsum, lsum), _ = jax.lax.scan(accum, (zeros, 0.0), mbs)
            grads = jax.tree.map(
                lambda g: g.astype(jnp.float32) / microbatch, gsum)
            loss = lsum / microbatch
        else:
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        if grad_compressor is not None:
            grads, opt_state = grad_compressor(grads, opt_state)
        new_opt, info = optimizer.update(grads, opt_state, step)
        new_params = opt_lib.cast_like_params(new_opt["master"], params)
        metrics = {"loss": loss, **info}
        return new_params, new_opt, metrics

    return train_step


def make_train_step(model: Model, cfg: ModelConfig, shape: ShapeSpec,
                    policy: ShardingPolicy, optimizer_name: str = "adamw",
                    microbatch: int = 1, peak_lr: float = 3e-4,
                    total_steps: int = 10000, accum_dtype=jnp.float32,
                    grad_compressor=None):
    """Returns (train_step_fn, optimizer) ready to jit/lower."""
    sched = opt_lib.cosine_schedule(peak_lr, warmup=min(500, total_steps // 10),
                                    total=total_steps)
    optimizer = (opt_lib.adafactor(sched) if optimizer_name == "adafactor"
                 else opt_lib.adamw(sched))
    fn = build_train_step(model, optimizer, policy, shape,
                          microbatch=microbatch, accum_dtype=accum_dtype,
                          grad_compressor=grad_compressor)
    return fn, optimizer


# ---------------------------------------------------------------------------
# serve steps
# ---------------------------------------------------------------------------

def make_prefill_step(model: Model, shape: ShapeSpec):
    def prefill_step(params, batch):
        logits, state = model.prefill(params, batch, max_len=shape.seq_len)
        return logits, state
    return prefill_step


def make_serve_step(model: Model, shape: ShapeSpec, sample_topk: int = 0):
    """One decode step: token -> logits -> (sampled) next token + new state.

    With sample_topk > 0 the next token comes from top-k sampling through
    the k-aware ``repro.sort`` front door (cfg.sort_method, default
    "auto"): vocab-sized logits with k ~ 50 are the textbook selection
    workload, so the planner routes them to radix-select, not a sort.
    """
    method = model.cfg.sort_method

    def serve_step(params, token, state, rng):
        logits, new_state = model.decode_step(params, token, state)
        if sample_topk:
            from repro import sort as sorting
            v, i = sorting.topk(logits, sample_topk, method=method)
            gumbel = -jnp.log(-jnp.log(
                jax.random.uniform(rng, v.shape) + 1e-9) + 1e-9)
            choice = jnp.argmax(v / 1.0 + gumbel, axis=-1)
            nxt = jnp.take_along_axis(i, choice[..., None], axis=-1)
        else:
            nxt = jnp.argmax(logits, axis=-1)[..., None]
        return nxt.astype(jnp.int32), new_state

    return serve_step
