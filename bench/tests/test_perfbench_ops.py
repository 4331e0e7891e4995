"""Each operation at a tiny size on the CPU: the front door agrees with the
plain reference, and the control (the reference one precision below)
and a result with truncated keys fail the comparison."""
import numpy as np
import pytest

from perfbench_tiny import TINY
from benchkit import cell, gen, spec

ONE_CHIP = [w for w, _ in TINY.items() if w != "partkey_sort_sf30_4chip"]


def _setup(workload, seed):
    c = spec.load_cell(workload)
    config_over, traffic_over = TINY[workload]
    ctx = cell.Ctx(config=cell.merged(c.config, config_over),
                   traffic=cell.merged(c.traffic, traffic_over))
    op = spec.op_module(ctx.traffic)
    inputs = gen.make_inputs(ctx.config, op.columns(ctx.traffic), 1, seed)[0]
    return ctx, op, inputs, gen.to_host(inputs)


def _exact(op, numbers):
    return all(numbers[k] <= op.LIMITS[k] for k in op.LIMITS)


@pytest.mark.parametrize("seed", [0, 2 ** 31 + 11])
@pytest.mark.parametrize("workload", ONE_CHIP)
def test_front_door_matches_reference(workload, seed):
    ctx, op, inputs, host = _setup(workload, seed)
    out = op.host_output(op.call(ctx, inputs))
    numbers = op.compare(ctx, host, out, op.reference(ctx, host))
    assert set(numbers) == set(op.LIMITS)
    assert _exact(op, numbers), numbers


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_control_fails(workload):
    ctx, op, _, host = _setup(workload, 3)
    numbers = op.compare(ctx, host, op.control(ctx, host),
                         op.reference(ctx, host))
    assert not _exact(op, numbers), numbers


@pytest.mark.parametrize("workload", ["q18_groupby_sf10",
                                      "partkey_sort_sf10"])
def test_truncated_keys_fail(workload):
    """The program's own output with each key cut to its low 8 bits."""
    ctx, op, inputs, host = _setup(workload, 4)
    out = list(op.host_output(op.call(ctx, inputs)))
    out[0] = (out[0] & 0xFF).astype(out[0].dtype)
    numbers = op.compare(ctx, host, tuple(out), op.reference(ctx, host))
    assert numbers["key_mismatches"] > 0


def test_topk_reference_breaks_ties_by_position():
    from benchkit.ops import topk
    x = np.array([[1.0, 3.0, 3.0, 2.0, 3.0, 0.5]], np.float32)
    v, i = topk.top_k_rows(x, 4)
    assert i.tolist() == [[1, 2, 4, 3]]
    assert v.tolist() == [[3.0, 3.0, 3.0, 2.0]]


def test_orderkeys_follow_tpch():
    ctx, op, _, host = _setup("q18_groupby_sf10", 5)
    keys = host["l_orderkey"]
    assert keys.shape == (4096,)
    assert np.all((keys - 1) % 32 < 8)
    lines = np.bincount(keys)[np.unique(keys)]
    assert lines.min() >= 1 and lines.max() <= 7
    assert np.all(host["l_quantity"] >= 1) and np.all(host["l_quantity"] <= 50)


def test_same_seed_same_inputs():
    a = _setup("partkey_sort_sf10", 2 ** 33 + 1)[3]
    b = _setup("partkey_sort_sf10", 2 ** 33 + 1)[3]
    c = _setup("partkey_sort_sf10", 1)[3]
    assert np.array_equal(a["l_partkey"], b["l_partkey"])
    assert not np.array_equal(a["l_partkey"], c["l_partkey"])
    assert np.array_equal(a["row_id"], np.arange(4096))


def test_api_bytes_are_counted_from_shapes():
    ctx, op, inputs, _ = _setup("partkey_sort_sf10", 0)
    assert op.keys_per_call(ctx, inputs) == 4096
    assert op.api_bytes_per_call(ctx, inputs) == 4 * 4096 * 4
    ctx, op, inputs, _ = _setup("dsv3_topk_sampling", 0)
    assert op.keys_per_call(ctx, inputs) == 8 * 1000
    assert op.api_bytes_per_call(ctx, inputs) == 8 * 1000 * 4 + 8 * 50 * 8
