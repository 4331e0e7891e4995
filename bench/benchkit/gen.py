"""The one input generator: a cell's columns, made on the device from the
seed in one jitted call.

A configuration describes each column by a ``kind`` and its parameters;
the traffic mix names the columns it uses and how many input sets
(``pool``) it cycles through.  Every seed gives the same shapes, so a
cell compiles once.  On several chips each column is split by row over
``mesh`` and every chip draws its own share, keyed by its index.

Kinds:

    uniform_int   int32, uniform in [low, high]
    row_id        int32, the row's global position
    normal        float32 (or ``dtype``), normal draws times ``scale``
    tpch_orderkey int32 TPC-H order keys: orders of 1-7 lines, keys sparse
                  (8 used of every 32), rows in a random order
"""
from __future__ import annotations

import zlib
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchkit.spec import SpecError, resolve

_U32 = 0xFFFFFFFF


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of up to 64 bits: ``jax.random.key`` keeps
    only the low 32, so the high word is folded in."""
    seed = int(seed) % (1 << 64)
    key = jax.random.key(seed & _U32)
    return jax.random.fold_in(key, (seed >> 32) & _U32)


def column_key(key: jax.Array, name: str, pool_index: int) -> jax.Array:
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & _U32)
    return jax.random.fold_in(key, pool_index)


def shape_of(config: Dict[str, Any], col: Dict[str, Any]) -> tuple:
    return tuple(int(resolve(config, s)) for s in col.get("shape", ["rows"]))


def _uniform_int(key, shape, col, shard, n_shards, config):
    return jax.random.randint(key, shape, int(col["low"]),
                              int(col["high"]) + 1, jnp.int32)


def _row_id(key, shape, col, shard, n_shards, config):
    return shard * shape[0] + jnp.arange(shape[0], dtype=jnp.int32)


def _normal(key, shape, col, shard, n_shards, config):
    dtype = jnp.dtype(col.get("dtype", "float32"))
    x = jax.random.normal(key, shape, jnp.float32) * float(col.get("scale",
                                                                   1.0))
    return x.astype(dtype)


def _tpch_orderkey(key, shape, col, shard, n_shards, config):
    """Each order draws its number of lines; its line slots that are used
    go first in a random order, the rows are the first ``shape[0]`` of
    them.  Where the lines drawn fall short of the rows, unused slots fill
    in, which only lengthens some orders up to ``lines_max``."""
    (n,) = shape
    orders = int(col["orders"]) // n_shards
    lo, hi = int(col["lines_min"]), int(col["lines_max"])
    k_lines, k_order = jax.random.split(key)
    lines = jax.random.randint(k_lines, (orders, 1), lo, hi + 1, jnp.int32)
    used = jnp.arange(hi, dtype=jnp.int32)[None, :] < lines
    g = shard * orders + jnp.arange(orders, dtype=jnp.int32)
    okey = (g // 8) * 32 + g % 8 + 1
    okey = jnp.broadcast_to(okey[:, None], (orders, hi)).reshape(-1)
    r = jax.random.bits(k_order, (orders * hi,), jnp.uint32) >> 1
    rank = jnp.where(used.reshape(-1), r, r | jnp.uint32(1 << 31))
    _, okey = jax.lax.sort((rank, okey), num_keys=1)
    if okey.shape[0] < n:
        raise SpecError(f"{orders} orders of at most {hi} lines cannot fill "
                        f"{n} rows")
    return okey[:n]


KINDS = {"uniform_int": _uniform_int, "row_id": _row_id, "normal": _normal,
         "tpch_orderkey": _tpch_orderkey}


def make_inputs(config: Dict[str, Any], names: Sequence[str], pool: int,
                seed: int, mesh: Optional[jax.sharding.Mesh] = None
                ) -> List[Dict[str, jax.Array]]:
    """``pool`` input sets, each a dict of the named columns, all made in
    one jitted call on the device (split by row over ``mesh`` if given)."""
    cols = config["columns"]
    for name in names:
        if name not in cols or cols[name]["kind"] not in KINDS:
            raise SpecError(f"column {name!r} has no known kind")
    n_shards = 1 if mesh is None else mesh.size
    shapes = {}
    for name in names:
        shape = shape_of(config, cols[name])
        if shape[0] % n_shards:
            raise SpecError(f"{name}: {shape[0]} rows do not split over "
                            f"{n_shards} chips")
        shapes[name] = (shape[0] // n_shards,) + shape[1:]

    def local(key, shard):
        return [{name: KINDS[cols[name]["kind"]](
                    column_key(key, name, p), shapes[name], cols[name],
                    shard, n_shards, config)
                 for name in names} for p in range(pool)]

    key = seed_key(seed)
    if mesh is None:
        return jax.jit(lambda k: local(k, 0))(key)
    from jax.sharding import PartitionSpec as P
    axis = mesh.axis_names[0]
    specs = [{name: P(axis) for name in names} for _ in range(pool)]
    fn = jax.shard_map(lambda k: local(k, jax.lax.axis_index(axis)),
                       mesh=mesh, in_specs=P(), out_specs=specs,
                       check_vma=False)
    return jax.jit(fn)(key)


def to_host(tree):
    """Device arrays -> numpy, whole (gathered from every shard)."""
    return jax.tree.map(np.asarray, jax.device_get(tree))
