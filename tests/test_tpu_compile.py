"""Compile-only checks of the relational path's device programs for one
chip of a described TPU v5e (``v5e:2x2``), at the shapes a scale-factor-1
TPC-H Q1 run gives them. Nothing runs on a chip: the TPU compiler refuses
here what the chip would refuse (tiling, memory, unsupported ops).

The topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports this file."""
import os

import jax
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.apps.tpch import LineitemQ1, q1_pricing_summary
from repro.core import Session, exprc, relops
from repro.data.synthetic import tpch_q1_lineitems

# each fused stage runs per batch of Session's default vector_rows (8192),
# padded to a power-of-two bucket; tail batches fall to the smallest one
BATCH_BUCKETS = (8, 8192)
# pre-aggregation reduces a whole partition at once: SF 1 (6,001,215 rows)
# over four partitions buckets to 2**21 rows, over one partition to 2**23
PARTITION_BUCKETS = (1 << 21, 1 << 23)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def q1_programs():
    """The jitted programs a Q1 run builds: run it once on a few rows on
    the host, with the device reducer forced on, and collect the fused
    stage cores and the segment-reduce kernels it compiled."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_AGG_DEVICE", "1")
        exprc.reset_kernel_cache()
        sess = Session(num_partitions=4, expr_backend="jax")
        ds = sess.load("lineitem", tpch_q1_lineitems(2000, seed=0),
                       LineitemQ1)
        q1_pricing_summary(sess.store, ds.set_name, session=sess).collect()
        cores = [k for k in exprc._KCACHE.values()
                 if getattr(k, "core", None) is not None]
        seg_kernels = dict(relops._SEG_KERNELS)
    assert len(cores) == 1, "Q1 fuses into one stage with a device core"
    assert seg_kernels, "Q1 pre-aggregation built no segment kernel"
    return cores[0], seg_kernels


@pytest.mark.parametrize("rows", BATCH_BUCKETS)
def test_q1_stage_core_compiles_for_v5e(one_chip, q1_programs, rows):
    core, _ = q1_programs
    args = [jax.ShapeDtypeStruct((rows,), d, sharding=one_chip)
            for d in core.core_dtypes]
    with jax.enable_x64(True):
        lowered = core.core.lower(*args)
        compiled = lowered.compile()
    outs = jax.tree.leaves(lowered.out_info)
    assert outs and all(o.shape == (rows,) for o in outs)
    assert any(o.dtype == np.float64 for o in outs)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= sum(
        rows * np.dtype(d).itemsize for d in core.core_dtypes)


@pytest.mark.parametrize("rows", PARTITION_BUCKETS)
def test_q1_segment_reducer_compiles_for_v5e(one_chip, q1_programs, rows):
    _, seg_kernels = q1_programs
    for (combiners, acc_dtypes, val_sig, _rows, segs), kern in \
            seg_kernels.items():
        # Q1: four sums, three means (a sum and a count each), one count
        assert len(combiners) == 11
        args = [jax.ShapeDtypeStruct((rows,), np.int64, sharding=one_chip)]
        args += [jax.ShapeDtypeStruct((rows,) + tuple(shape), np.dtype(dt),
                                      sharding=one_chip)
                 for dt, shape in val_sig]
        with jax.enable_x64(True):
            lowered = kern.lower(*args)
            compiled = lowered.compile()
        outs = jax.tree.leaves(lowered.out_info)
        assert [str(o.dtype) for o in outs] == list(acc_dtypes)
        assert all(o.shape == (segs,) for o in outs)
        mem = compiled.memory_analysis()
        assert mem.argument_size_in_bytes < 16 * 2 ** 30
