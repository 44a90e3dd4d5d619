"""Compile the chip's hot path for a described TPU v5e, no chip attached.

Interpret mode (every other kernel test) cannot see what Mosaic
refuses: primitives with no TPU lowering, i1 vectors, 64-bit scalars,
or more scoped VMEM than a kernel may use.  Each test here compiles at
the paper's widths (batch 16384, m_pad up to 8192) and asserts the
Pallas kernel is in the compiled program, under its own name
(``rgb_lp``), which a profile of the chip shows.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and pytest-xdist
workers import every test file.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.batch_lp import _pick_tile, rgb_pallas
from repro.serve_lp.buckets import ExecSpec
from repro.serve_lp.mesh_layout import plan_layout
from repro.serve_lp.sharding import _make_solve, group_launch
from repro.solver import SolverSpec
from repro.solver.spec import DEFAULT_M
from repro.tune import TuningTable, use_table

BATCH = 16384          # paper Fig. 3c batch
# The Mosaic custom call, named after the kernel's pallas_call.
KERNEL = re.compile(r'%rgb_lp[\w.]* = .*custom_call_target="tpu_custom_call"')
SERVED_M, SERVED_B = 4096, 256


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


def _shapes(rows, m_pad, sharding, dtype=jnp.float32):
    return (jax.ShapeDtypeStruct((rows, 4, m_pad), dtype, sharding=sharding),
            jax.ShapeDtypeStruct((rows, 2), dtype, sharding=sharding),
            jax.ShapeDtypeStruct((rows, 1), jnp.int32, sharding=sharding))


def _kernel_text(sharding, m_pad, chunk):
    tile = _pick_tile(m_pad, BATCH)
    rows = -(-BATCH // tile) * tile       # as the solver pads the batch
    fn = jax.jit(functools.partial(rgb_pallas, M=DEFAULT_M, tile=tile,
                                   chunk=chunk))
    return fn.lower(*_shapes(rows, m_pad, sharding)).compile().as_text()


@pytest.mark.parametrize("m_pad", [128, 1024, 8192])
def test_kernel_compiles_dense(one_chip, m_pad):
    assert KERNEL.search(_kernel_text(one_chip, m_pad, 0))


def test_kernel_compiles_chunked(one_chip):
    assert KERNEL.search(_kernel_text(one_chip, 8192, 128))


@pytest.mark.parametrize("chunk", [0, 128])
def test_kernel_compiles_under_x64(one_chip, chunk):
    # float32 kernel with jax_enable_x64 on, as the serving entrypoint
    # runs it: no Python scalar may reach Mosaic as i64/f64.
    with jax.enable_x64(True):
        assert KERNEL.search(_kernel_text(one_chip, 8192, chunk))


def _served_spec(n_devices):
    with use_table(TuningTable()):
        pinned = SolverSpec(backend="kernel", interpret=False) \
            .resolve_for_shape(SERVED_M, SERVED_B)
    return ExecSpec(bucket_m=SERVED_M, b_pad=SERVED_B, solver=pinned,
                    n_devices=n_devices)


def test_served_executable_compiles(topo):
    spec = _served_spec(1)
    layout = plan_layout(spec.b_pad, spec.tile, 1)
    fn, placement = group_launch(_make_solve(spec), [topo.devices[0]])
    text = fn.lower(*_shapes(layout.b_pad, SERVED_M, placement)) \
        .compile().as_text()
    assert KERNEL.search(text)


def test_served_executable_compiles_on_four_chips(topo):
    # The scheduler's default on a 4-chip host: the flush is dealt over
    # every device in at most two shard_map launch groups.
    spec = _served_spec(4)
    layout = plan_layout(spec.b_pad, spec.tile, 4)
    assert sum(g.n_devices for g in layout.groups) == 4
    for g in layout.groups:
        devs = list(topo.devices[g.start:g.start + g.n_devices])
        fn, placement = group_launch(_make_solve(spec), devs)
        text = fn.lower(*_shapes(g.rows, SERVED_M, placement)) \
            .compile().as_text()
        assert KERNEL.search(text)
