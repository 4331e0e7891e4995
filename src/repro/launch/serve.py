"""Serving driver: prefill + decode with a length-sorted batch scheduler.

The scheduler is the third place the paper's technique lands in the
framework (after MoE routing and sampling): incoming requests are sorted by
prompt length (any registered ``repro.sort`` backend) so each prefill batch is
length-homogeneous — padding waste drops from worst-case to
max-within-bucket, exactly the data-movement argument of the paper applied
to request scheduling.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --smoke \
      --requests 16 --decode-steps 32
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import pathlib
import time
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import sort as sorting
from repro.configs.base import get_config, get_smoke_config
from repro.core import tuning as _tuning
from repro.obs import metrics as _metrics, report as _obs_report, \
    trace as _obs
from repro.launch import steps as steps_lib
from repro.launch.mesh import dp_axes_of, make_host_mesh
from repro.models.model_zoo import build
from repro.sharding.partitioning import ShardingPolicy

# where serve persists its tuning-profile snapshot between runs (the
# --state-dir flag overrides; unset means no persistence)
SERVE_STATE_ENV = "REPRO_SERVE_STATE_DIR"


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray          # (len,) int32
    max_new: int = 32
    out: Optional[np.ndarray] = None
    submit_t: float = 0.0       # monotonic clock at submit()


class LengthSortedScheduler:
    """Batch requests by sorted prompt length (paper technique #3).

    Each batch is **anchored at the oldest queued request** and filled with
    its adjacent-length neighbours from the sorted order (the window with
    the smallest length spread that contains the anchor).  Pure
    shortest-k scheduling starved long prompts forever under sustained
    load — a long request could sit at the tail of the sorted order while
    fresh short requests kept overtaking it; anchoring bounds every
    request's wait at its arrival backlog while keeping batches
    length-homogeneous (the padding-waste argument survives intact).

    ``method`` takes any registered backend name; the default ``"auto"`` lets
    the engine's cost-model planner pick per queue size, so the scheduler
    scales from a handful of requests to engine-sized backlogs unchanged.

    With a ``mesh`` (any multi-device host or pod slice) the backlog sort
    itself goes distributed: a (length, position) composite key is sorted
    globally over the mesh by the sample-sort (``axis_name`` follows
    ``distributed_sort`` — one axis, a tuple, or ``None`` for the whole
    mesh), so a fleet-scale queue never funnels through one device.
    Single-device meshes and backlogs under ``distributed_min`` keep the
    local argsort path — per-queue-length shard_map programs only pay off
    once the backlog reaches engine scale.
    """

    def __init__(self, batch_size: int, method: str = "auto", *,
                 mesh=None, axis_name=None,
                 distributed_min: int = 4096):
        self.batch_size = batch_size
        self.method = method
        self.mesh = mesh
        self.axis_name = axis_name
        self.distributed_min = distributed_min
        self.queue: List[Request] = []

    def submit(self, req: Request) -> None:
        req.submit_t = time.monotonic()
        self.queue.append(req)

    def _n_dev(self) -> int:
        if self.mesh is None:
            return 1
        from repro.engine import samplesort
        axes = samplesort._axes_tuple(self.mesh, self.axis_name)
        return samplesort._n_dev(self.mesh, axes)

    def _order(self, lens: jnp.ndarray) -> np.ndarray:
        n = lens.shape[0]
        idx_bits = max(1, (n - 1).bit_length())
        distributed = (self._n_dev() > 1
                       and n >= self.distributed_min
                       and int(jnp.max(lens)) < (1 << (31 - idx_bits)))
        if not distributed:
            return np.array(sorting.argsort(lens, method=self.method))
        # mesh path: value-sort a packed (length, position) composite —
        # the distributed path has no argsort, but the composite is one
        comp = (lens.astype(jnp.int32) << idx_bits) \
            | jnp.arange(n, dtype=jnp.int32)
        out = sorting.sort(comp, mesh=self.mesh, axis_name=self.axis_name)
        return np.array(out) & ((1 << idx_bits) - 1)

    def next_batch(self) -> List[Request]:
        if not self.queue:
            return []
        lens_np = np.asarray([len(r.prompt) for r in self.queue],
                             dtype=np.int32)
        order = self._order(jnp.asarray(lens_np))
        n, b = len(self.queue), min(self.batch_size, len(self.queue))
        # anchor: the oldest queued request (the queue is submission
        # order, so position 0 is it) — every batch serves the current
        # oldest, which bounds any request's wait at its arrival backlog
        order = np.asarray(order)
        anchor = int(np.nonzero(order == 0)[0][0])
        # lengths in schedule order are ascending, so a window's spread is
        # just last-minus-first — O(b) over the candidate starts
        sl = lens_np[order]
        best_start, best_spread = None, None
        for start in range(max(0, anchor - b + 1), min(anchor, n - b) + 1):
            spread = int(sl[start + b - 1] - sl[start])
            if best_spread is None or spread < best_spread:
                best_start, best_spread = start, spread
        window = order[best_start:best_start + b]
        batch = [self.queue[i] for i in window]
        picked = set(int(i) for i in window)
        self.queue = [r for i, r in enumerate(self.queue)
                      if i not in picked]
        return batch

    def padding_waste(self, batch: List[Request]) -> float:
        if not batch:
            return 0.0
        lens = [len(r.prompt) for r in batch]
        return 1.0 - sum(lens) / (len(lens) * max(lens))


def resolve_state_dir(explicit: Optional[str] = None
                      ) -> Optional[pathlib.Path]:
    """The serve state directory: the explicit argument, else the
    ``REPRO_SERVE_STATE_DIR`` environment variable, else None (no
    persistence)."""
    d = explicit if explicit is not None \
        else os.environ.get(SERVE_STATE_ENV)
    return pathlib.Path(d) if d else None


def restore_state(state_dir: os.PathLike) -> List[str]:
    """Restore a previous run's tuning-profile snapshot from ``state_dir``
    into the ambient tuning state.  The restore is identity-gated: a
    profile whose device fingerprint differs (snapshot copied from another
    machine) is skipped, never trusted.  Returns the names of what was
    restored (for the startup log line)."""
    restored: List[str] = []
    pp = _tuning.profile_path(directory=pathlib.Path(state_dir))
    if pp.is_file():
        try:
            prof = _tuning.load(pp)
            if prof.fingerprint == _tuning.device_fingerprint():
                _tuning.set_active(dataclasses.replace(
                    prof, source="persisted"))
                restored.append("tuning profile")
        except _tuning.ProfileError:
            pass
    return restored


def snapshot_state(state_dir: os.PathLike) -> List[pathlib.Path]:
    """Snapshot the ACTIVE TuningProfile into ``state_dir`` so the next
    run starts from this run's calibration instead of the platform
    defaults.  Returns the written paths."""
    prof = _tuning.active()
    return [_tuning.save(prof, _tuning.profile_path(
        prof.fingerprint, directory=pathlib.Path(state_dir)))]


def batch_accounting(done: List[Request]):
    """Per-prompt-length accounting of the completed requests — ONE
    ``relational.group_by`` (prompt length -> generated-token count) with
    ``agg=("count", "mean")``, i.e. the serving ledger expressed as the
    sort subsystem's group-by aggregate.  Returns ascending
    ``[(prompt_len, n_requests, mean_new_tokens), ...]``."""
    from repro import relational
    if not done:
        return []
    lens = jnp.asarray([len(r.prompt) for r in done], dtype=jnp.int32)
    gen = jnp.asarray([0 if r.out is None else len(r.out) for r in done],
                      dtype=jnp.int32)
    gb = relational.group_by(lens, gen, agg=("count", "mean"))
    g = int(gb.n_groups)
    keys = np.asarray(gb.keys[:g])
    cnt = np.asarray(gb.aggregates[0][:g])
    mean = np.asarray(gb.aggregates[1][:g])
    return [(int(k), int(c), float(m)) for k, c, m in zip(keys, cnt, mean)]


def serve(arch: str, smoke: bool = True, n_requests: int = 16,
          batch_size: int = 8, decode_steps: int = 32, topk: int = 50,
          seed: int = 0, max_len: int = 256,
          distributed_queue: Optional[bool] = None,
          state_dir: Optional[str] = None):
    """``distributed_queue`` routes the scheduler's backlog sort over the
    host mesh (defaults to on whenever the host offers >1 device).

    ``state_dir`` (or ``REPRO_SERVE_STATE_DIR``) makes the server
    stateful across restarts: on startup it restores the snapshotted
    TuningProfile (identity-gated), on shutdown it snapshots whatever is
    active — so a calibration paid once keeps pricing plans
    across process restarts."""
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    mesh = make_host_mesh()
    sdir = resolve_state_dir(state_dir)
    if sdir is not None:
        got = restore_state(sdir)
        if got:
            print(f"[serve] restored {' + '.join(got)} from {sdir}")
    if distributed_queue is None:
        distributed_queue = mesh.shape["data"] > 1
    policy = ShardingPolicy(mesh=mesh, dp_axes=dp_axes_of(mesh))
    model = build(cfg, policy=policy)
    key = jax.random.PRNGKey(seed)
    params, _ = model.init(key)

    from repro.configs.base import ShapeSpec
    shape = ShapeSpec("serve", max_len, batch_size, "decode")
    serve_step = jax.jit(steps_lib.make_serve_step(model, shape,
                                                   sample_topk=topk))

    rng = np.random.default_rng(seed)
    sched = LengthSortedScheduler(
        batch_size, method=cfg.sort_method,
        mesh=mesh if distributed_queue else None)
    for rid in range(n_requests):
        plen = int(rng.integers(4, max_len // 4))
        sched.submit(Request(rid=rid, prompt=rng.integers(
            0, cfg.vocab_size, plen).astype(np.int32),
            max_new=decode_steps))

    done: List[Request] = []
    stats = {"batches": 0, "padding_waste": [], "decode_tps": []}
    try:
        _serve_loop(sched, model, params, serve_step, cfg, rng, key,
                    decode_steps, max_len, done, stats)
    finally:
        # shutdown snapshot — also on an exception mid-run, so a
        # calibration paid this run is never lost
        if sdir is not None:
            for p in snapshot_state(sdir):
                print(f"[serve] state snapshot -> {p}")
    waste = float(np.mean(stats["padding_waste"]))
    print(f"[serve] {len(done)} requests in {stats['batches']} batches; "
          f"mean padding waste {waste:.3f}; "
          f"decode {np.mean(stats['decode_tps']):.1f} tok/s")
    acct = batch_accounting(done)
    stats["length_groups"] = acct
    if acct:
        head = ", ".join(f"len={k}: {c} req x {m:.0f} tok"
                         for k, c, m in acct[:8])
        more = "" if len(acct) <= 8 else f" (+{len(acct) - 8} more)"
        print(f"[serve] length accounting: {head}{more}")
    if _obs.enabled():
        print(_obs_report.slo_report())
    return done, stats


def _serve_loop(sched, model, params, serve_step, cfg, rng, key,
                decode_steps, max_len, done, stats):
    while True:
        batch = sched.next_batch()
        if not batch:
            break
        stats["batches"] += 1
        stats["padding_waste"].append(sched.padding_waste(batch))
        if _obs.enabled():
            now = time.monotonic()
            for r in batch:
                _metrics.histogram("serve.queue_wait_ms").observe(
                    (now - r.submit_t) * 1e3)
            _metrics.histogram("serve.padding_waste").observe(
                stats["padding_waste"][-1])
            _metrics.counter("serve.requests").inc(len(batch))
        plen = max(len(r.prompt) for r in batch)
        toks = np.zeros((len(batch), plen), np.int32)
        for i, r in enumerate(batch):   # left-pad to common length
            toks[i, plen - len(r.prompt):] = r.prompt
        feed = {"tokens": jnp.asarray(toks)}
        if model.is_encdec:
            feed["frames"] = jnp.asarray(rng.standard_normal(
                (len(batch), cfg.enc_seq, cfg.d_model)) * 0.1,
                dtype=jnp.float32)
        logits, state = model.prefill(params, feed, max_len=max_len)
        nxt = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        outs = [nxt]
        t0 = time.monotonic()
        for i in range(decode_steps - 1):
            nxt, state = serve_step(params, nxt, state,
                                    jax.random.fold_in(key, i))
            outs.append(nxt)
        dt = time.monotonic() - t0
        stats["decode_tps"].append(
            (decode_steps - 1) * len(batch) / max(dt, 1e-9))
        gen = np.concatenate([np.array(o) for o in outs], axis=1)
        fin = time.monotonic()
        for i, r in enumerate(batch):
            r.out = gen[i]
            done.append(r)
            if _obs.enabled():
                _metrics.histogram("serve.e2e_ms").observe(
                    (fin - r.submit_t) * 1e3)
        if _obs.enabled():
            _metrics.gauge("serve.decode_tps").set(stats["decode_tps"][-1])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--decode-steps", type=int, default=32)
    ap.add_argument("--topk", type=int, default=50)
    ap.add_argument("--distributed-queue", default=None,
                    action=argparse.BooleanOptionalAction,
                    help="sort the request backlog over the host mesh "
                         "(--no-distributed-queue forces the local path; "
                         "default: on when the host has >1 device)")
    ap.add_argument("--state-dir", default=None,
                    help="directory for the tuning-profile snapshot "
                         "restored on startup and written on shutdown "
                         f"(default: ${SERVE_STATE_ENV} if set)")
    args = ap.parse_args()
    serve(args.arch, smoke=args.smoke, n_requests=args.requests,
          batch_size=args.batch_size, decode_steps=args.decode_steps,
          topk=args.topk, distributed_queue=args.distributed_queue,
          state_dir=args.state_dir)


if __name__ == "__main__":
    main()
