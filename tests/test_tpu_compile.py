"""The main path's Pallas kernels compile for a TPU v5e at real widths.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology (``jax.experimental.topologies``) and every program must hold the
Mosaic kernel (``tpu_custom_call``), so a kernel that interpret mode
accepts but Mosaic refuses (a lane reshape, an unaligned block, a
primitive with no TPU lowering, too much VMEM) fails here and not on the
chip.  The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and it keeps it until it exits.
"""
from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.dtype(d), sharding=one_chip)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _bitonic(**kw):
    from repro.kernels import bitonic_sort as bs
    return lambda x: bs.sort_blocks(x, interpret=False, **kw)


def _bitonic_kv(x, v):
    from repro.kernels import bitonic_sort as bs
    return bs.sort_kv_blocks(x, v, interpret=False)


def _topk(x):
    from repro.kernels import bitonic_sort as bs
    return bs.topk_blocks(x, 64, interpret=False)


def _merge(z):
    from repro.kernels import merge_path as mp
    return mp.merge_flat(z, l=z.shape[0] // 2, interpret=False)


def _merge_kv(z, v):
    from repro.kernels import merge_path as mp
    return mp.merge_flat(z, v, l=z.shape[0] // 2, interpret=False)


def _radix(x):
    from repro.kernels import radix_sort as rs
    return rs.sort_blocks(x, interpret=False)


def _radix_kv(x, v):
    from repro.kernels import radix_sort as rs
    return rs.sort_kv_blocks(x, v, interpret=False)


def _cas(a, b):
    from repro.kernels import bitserial_cas as bc
    return bc.cas_blocks(a, b, width=4, interpret=False)


def _select(x):
    from repro.kernels import radix_select as sel
    return sel.select_topk(x, 64, use_kernel=True, interpret=False)


CASES = {
    "bitonic-8x2048-f32": (_bitonic(), [((8, 2048), "float32")]),
    "bitonic-1x65536-f32": (_bitonic(), [((1, 65536), "float32")]),
    "bitonic-desc-8x2048-i32": (_bitonic(descending=True),
                                [((8, 2048), "int32")]),
    "bitonic-bf16-8x2048": (_bitonic(), [((8, 2048), "bfloat16")]),
    "bitonic-13x2048-f32": (_bitonic(), [((13, 2048), "float32")]),
    "bitonic-kv-8x2048": (_bitonic_kv, [((8, 2048), "float32"),
                                        ((8, 2048), "int32")]),
    "bitonic-kv-1x65536-u32": (_bitonic_kv, [((1, 65536), "uint32"),
                                             ((1, 65536), "int32")]),
    "bitonic-topk-8x2048-k64": (_topk, [((8, 2048), "float32")]),
    "merge-path-1x65536": (_merge, [((65536,), "float32")]),
    "merge-path-1x65536-u32": (_merge, [((65536,), "uint32")]),
    "merge-path-kv-1x65536": (_merge_kv, [((65536,), "float32"),
                                          ((65536,), "int32")]),
    "radix-1x2^20": (_radix, [((1, 1 << 20), "uint32")]),
    "radix-kv-1x2^20": (_radix_kv, [((1, 1 << 20), "uint32"),
                                    ((1, 1 << 20), "int32")]),
    "radix-13x1000": (_radix, [((13, 1000), "uint32")]),
    "radix-select-8x4096-k64": (_select, [((8, 4096), "float32")]),
    "bitserial-cas-13x128-w4": (_cas, [((13, 128), "uint32")] * 2),
}


@pytest.mark.parametrize("name", list(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    compiled = _compile(one_chip, fn, *shapes)
    assert "tpu_custom_call" in compiled.as_text()
