"""The xla backend's key-value sort against the argsort-then-gather form.

``XlaBackend.sort_kv`` carries the payload through one stable two-operand
sort.  Its outputs must be bit-identical to gathering keys and payload by
``jnp.argsort(keys, stable=True)`` for every dtype, in both directions,
ties, NaN, infinities and signed zeros included, and the compiled program
must hold no gather.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.sortspec import get_backend

DTYPES = ("int32", "uint32", "int8", "float32", "bfloat16")
SHAPES = ((1, 0), (1, 1), (3, 1000), (1, 4097))
FLOAT_SPECIALS = (np.nan, -np.nan, np.inf, -np.inf, -0.0, 0.0)


def _keys(dtype, shape, dups, rng):
    dt = jnp.dtype(dtype)
    if jnp.issubdtype(dt, jnp.floating):
        if dups:   # 7 distinct values, NaN among them
            pool = np.array(FLOAT_SPECIALS[1:] + (1.5, -2.0), np.float32)
            raw = rng.choice(pool, size=shape)
        else:
            raw = rng.normal(scale=100.0, size=shape).astype(np.float32)
            mask = rng.random(shape) < 0.1
            raw[mask] = rng.choice(np.array(FLOAT_SPECIALS, np.float32),
                                   size=int(mask.sum()))
        return jnp.asarray(raw).astype(dt)
    info = np.iinfo(dt)
    if dups:       # 8 distinct values, both extremes among them
        pool = np.array([info.min, info.max, 0, 1, 2, 3, info.max - 1,
                         info.min + 1], dtype=dt)
        return jnp.asarray(rng.choice(pool, size=shape))
    return jnp.asarray(rng.integers(info.min, info.max, size=shape,
                                    dtype=dt, endpoint=True))


def _bits(x):
    a = np.asarray(x)
    return a.view(np.dtype(f"u{a.dtype.itemsize}"))


@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("dups", (True, False), ids=("dups", "spread"))
@pytest.mark.parametrize("descending", (False, True), ids=("asc", "desc"))
@pytest.mark.parametrize("dtype", DTYPES)
def test_sort_kv_matches_argsort_gather(dtype, descending, dups, shape):
    rng = np.random.default_rng(
        [DTYPES.index(dtype), descending, dups, *shape])
    keys = _keys(dtype, shape, dups, rng)
    values = jnp.asarray(rng.permutation(keys.size).reshape(shape)
                         .astype(np.int32))
    order = jnp.argsort(keys, axis=-1, stable=True, descending=descending)
    want_k = jnp.take_along_axis(keys, order, axis=-1)
    want_v = jnp.take_along_axis(values, order, axis=-1)
    got_k, got_v = get_backend("xla").sort_kv(keys, values,
                                              descending=descending)
    assert got_k.dtype == keys.dtype and got_v.dtype == values.dtype
    np.testing.assert_array_equal(_bits(got_k), _bits(want_k))
    np.testing.assert_array_equal(_bits(got_v), _bits(want_v))


@pytest.mark.parametrize("descending", (False, True), ids=("asc", "desc"))
def test_sort_kv_compiles_without_gather(descending):
    spec = jax.ShapeDtypeStruct((2, 1024), jnp.int32)
    hlo = jax.jit(lambda k, v: get_backend("xla").sort_kv(
        k, v, descending=descending)).lower(spec, spec).compile().as_text()
    ops = re.findall(r"\s([a-z-]+)\(", hlo)
    assert "sort" in ops
    assert "gather" not in ops
