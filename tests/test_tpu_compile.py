"""The main path's kernels compile for a TPU v5e at the shapes the solver
produces at 50,000 tasks x 10,000 nodes, without a chip: the TPU compiler
is installed here and compiles for a described ``v5e:2x2`` topology. This
catches what interpret mode cannot (Mosaic's op and dtype limits, SMEM and
VMEM budgets) at no chip time. Nothing runs, so it says nothing about
results or times.

The topology is described only inside the module fixture: describing it
loads libtpu, which one process at a time may hold, so it must never
happen at import or collection, where every xdist worker would try.
"""

import numpy as np
import pytest

import jax

from volcano_tpu.ops import pallas_allocate as pa
from volcano_tpu.ops.score import ScoreWeights
from volcano_tpu.utils.synth import synth_arrays

N_TASKS, N_NODES = 50_000, 10_000


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else logs in /tmp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def synth():
    return synth_arrays(N_TASKS, N_NODES, gang_size=8, n_queues=4)


def _shapes(arrays, shardings):
    return [jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                 sharding=s)
            for a, s in zip(arrays, shardings)]


def _weights(sharding, r):
    w = ScoreWeights.make(r, binpack=1.0)
    return ScoreWeights(*_shapes(w, [sharding] * len(w)))


def _pallas_args(sa, sharding):
    """_gang_allocate_pallas_jit's positional inputs: the per-group bucket
    row stands in for task_bucket, and it takes no job_queue."""
    args = list(sa.args)
    args[6] = np.full(sa.group_req.shape[0], -1, np.int32)
    del args[12]
    return _shapes(args, [sharding] * len(args)) + \
        [_weights(sharding, sa.group_req.shape[1])]


@pytest.mark.parametrize("ns_live", [False, True])
def test_pallas_gang_allocate_compiles(one_chip, synth, ns_live):
    compiled = pa._gang_allocate_pallas_jit.lower(
        *_pallas_args(synth, one_chip), allow_pipeline=True,
        ns_live=ns_live).compile()
    assert "tpu_custom_call" in compiled.as_text()


# the dgxmig5k-burst cell's burst call: ~14,000 pending jobs, one group
# each, over 5,000 nodes (5,120 columns) in ten resource dimensions, so
# the kernel's resource axis takes 16 sublanes
WIDE_GROUPS, WIDE_NODES, WIDE_R = 14_336, 5_000, 10


def _wide():
    return synth_arrays(WIDE_GROUPS, WIDE_NODES, gang_size=1, r=WIDE_R,
                        n_queues=8, seed=25)


@pytest.fixture(scope="module")
def wide():
    return _wide()


def test_pallas_wide_resource_axis_compiles(one_chip, wide):
    assert pa.resource_pad(WIDE_R) == 16
    compiled = pa._gang_allocate_pallas_jit.lower(
        *_pallas_args(wide, one_chip), allow_pipeline=True,
        ns_live=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pallas_wide_resource_axis_equals_chunked_on_the_chip(wide):
    """Runs both kernels on one seeded call at the cell's shapes: only
    where JAX has the chip. Under pytest the suite's conftest holds JAX
    to the CPU, so on the chip it runs as ``python -m
    tests.test_tpu_compile``."""
    if jax.default_backend() != "tpu":
        pytest.skip("runs the kernels: needs a TPU backend")
    from volcano_tpu.ops.allocate import gang_allocate_chunked
    w = ScoreWeights.make(WIDE_R, binpack=5.0)
    args = [jax.numpy.asarray(a) for a in wide.args] + [w]
    want = [np.asarray(x) for x in gang_allocate_chunked(*args)[:4]]
    got = [np.asarray(x) for x in pa.gang_allocate_pallas(*args)[:4]]
    assert (got[0] >= 0).sum() > WIDE_GROUPS // 2
    for name, a, b in zip(("assign", "pipelined", "ready", "kept"),
                          want, got):
        assert np.array_equal(a, b), name


def test_chunked_kernel_compiles(one_chip, synth):
    from volcano_tpu.ops.allocate import gang_allocate_chunked
    args = _shapes(synth.args, [one_chip] * len(synth.args))
    compiled = gang_allocate_chunked.lower(
        *args, _weights(one_chip, synth.group_req.shape[1])).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 0


def test_victim_kernel_compiles(one_chip):
    """chip_smoke.victim_env's widths: 1,000 preemptors over 2,000
    nodes, eight victims a node."""
    from volcano_tpu.ops.victims import victim_prefix_batch
    b, n, v, r = 1000, 2000, 8, 4
    f32 = np.float32
    args = [np.zeros((b, r), f32), np.zeros((b, n), bool),
            np.zeros((n, r), f32), np.zeros((n, v, r), f32),
            np.zeros((n, v), bool), np.zeros(r, f32)]
    compiled = jax.jit(victim_prefix_batch()).lower(
        *_shapes(args, [one_chip] * len(args))).compile()
    assert compiled.memory_analysis().argument_size_in_bytes > 0


def test_sharded_kernel_compiles(topo):
    """The node-sharded kernel on a 4-chip mesh, the shortlist-union width
    the pruned 50k x 10k cycle hands it (2,560 nodes)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from volcano_tpu.ops.sharded import (make_sharded_gang_allocate,
                                         synth_shardings)
    mesh = Mesh(np.array(topo.devices[:4]), ("nodes",))
    sa = synth_arrays(N_TASKS, 2560, gang_size=8, n_queues=4)
    args = _shapes(sa.args, synth_shardings(mesh))
    w = _weights(NamedSharding(mesh, P()), sa.group_req.shape[1])
    compiled = make_sharded_gang_allocate(mesh).lower(*args, w).compile()
    text = compiled.as_text()
    assert "all-gather" in text or "all-reduce" in text


@pytest.mark.parametrize("n_tasks,fits", [(140_000, True),
                                          (150_000, False)])
def test_pallas_smem_budget_brackets_the_compiler(one_chip, n_tasks, fits):
    """solver._select_kernel routes batches past fits_smem to the chunked
    kernel; the bound sits below what Mosaic accepts on v5e. Gangs of 8,
    so jobs = groups = tasks / 8."""
    t, j = n_tasks, n_tasks // 8
    assert pa.fits_smem(t, j, 8, j) is fits
    i32, f32 = np.int32, np.float32
    n = 256
    args = [np.zeros(t, i32)] + [np.zeros(j, i32)] * 4 + \
        [np.zeros(8, i32)] * 4 + [np.zeros(j, i32)] * 2 + [
            np.zeros((j, 8), f32), np.zeros((8, 128), f32),
            np.zeros((8, 128), f32), np.zeros((8, 128), i32),
            np.zeros((8, 8), f32), np.zeros((8, 8), f32),
            np.zeros((8, 128), f32), np.zeros((1, 128), f32),
            np.zeros((8, 128), f32), np.zeros((8, n), f32),
            np.zeros((8, n), f32), np.zeros((8, n), f32),
            np.zeros((1, n), i32), np.zeros((1, n), i32),
            np.zeros((1, 128), f32), np.zeros((1, 128), f32),
            np.zeros((j, 1, n), f32)]
    lowered = pa._pallas_gang_allocate.lower(
        *_shapes(args, [one_chip] * len(args)), n_res=4,
        allow_pipeline=True, ns_live=False)
    if fits:
        assert "tpu_custom_call" in lowered.compile().as_text()
    else:
        with pytest.raises(Exception, match="smem"):
            lowered.compile()


if __name__ == "__main__":
    # on the chip, outside pytest: the R = 10 kernel against the chunked
    # kernel at the cell's shapes
    test_pallas_wide_resource_axis_equals_chunked_on_the_chip(_wide())
    print("pallas (R = 10, 16 sublanes) equals the chunked kernel")
