"""Ahead-of-time compiles of the main path's device programs for a TPU v5e.

Each case lowers a kernel at a real size for one chip of a described
``v5e:2x2`` topology and compiles it with the TPU compiler, without a
chip: the compiler refuses what the chip would refuse (tiling, on-chip
memory, a program too large for the device). Nothing runs, so these say
nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import fault as F, lp, netsim as NS, routing as R, \
    synthesis as SY, topology as T

DEVICE_BYTES = 16 * 10**9       # HBM of one TPU v5e chip


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _on(sharding, args):
    return tuple(jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                      sharding=sharding) for a in args)


def _assert_fits(compiled):
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    assert 0 < total < DEVICE_BYTES, total


def _compile_sweep(one_chip, tables, rates, **kw):
    call = NS._sweep_call(tables, rates, kw.pop("traffic", None),
                          cycles=1200, warmup=400, slots=128, seed=0,
                          flits=4, kernel="csr", patience=64,
                          watchdog=512, **kw)
    assert call.fn is NS._sweep_csr
    return call.fn.lower(*_on(one_chip, call.args),
                         **call.static).compile()


def test_sweep_csr_static_12cube(one_chip):
    """The 12^3 saturation sweep's kernel: DOR tables, 10 rates."""
    tab = NS.dor_tables(T.pt((12, 12, 12)))
    compiled = _compile_sweep(one_chip, tab, np.arange(1, 11) * 0.05,
                              adaptive=None, fault=None)
    _assert_fits(compiled)


def test_sweep_csr_adaptive_fault_8cube(one_chip):
    """Adaptive routing with a mid-sweep OCS fault at 8^3, with the
    four-VC queue layout that escape-reserving tables use."""
    topo = T.pt((8, 8, 8))
    tab = NS.dor_tables(topo, n_vc=4)
    at = R.allowed_turns(topo, n_vc=2, priority="apl")
    ev = F.fault_event(at, F.colors_in_use(topo)[0], 600)
    spec = NS.adaptive_spec(topo, dead_channels=ev[1])
    compiled = _compile_sweep(one_chip, tab, [0.05, 0.1, 0.2, 0.4],
                              adaptive=spec, fault=ev)
    _assert_fits(compiled)


def test_sweep_csr_phased_adaptive_4x4x8(one_chip):
    """Trace replay with adaptive routing on a 128-chip slice: the eight
    dispatch and combine phases of a DeepSeek-V3 expert-parallel decode
    step, three rates."""
    from repro.core import workload as W
    topo = T.pt((4, 4, 8))
    tab = NS.dor_tables(topo, n_vc=4)
    trace = W.ep_decode_trace(topo.pod, "DeepSeek-V3", tokens_per_chip=64,
                              layers=4, period=768, seed=0,
                              experts_per_chip=2)
    compiled = _compile_sweep(one_chip, tab, [0.2, 0.37, 0.49],
                              traffic=trace, adaptive=NS.adaptive_spec(topo),
                              fault=None)
    _assert_fits(compiled)


def test_pdhg_chunk_f64_synthesis_lp(one_chip):
    """One PDHG chunk in f64 on the 8^3 synthesis LP's operator."""
    A = SY.build_synthesis_lp(T.Pod((8, 8, 8))).A
    (m, n), nnz = A.shape, len(A.vals)
    with jax.enable_x64(True):
        def s(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
        f64 = jnp.float64
        args = (s((nnz,), jnp.int32), s((nnz,), jnp.int32), s((nnz,), f64),
                s((n,), f64), s((m,), f64), s((n,), f64), s((n,), f64),
                s((n,), f64), s((m,), f64), s((), f64), s((), f64))
        compiled = lp._pdhg_chunk.lower(*args, m=m, n=n,
                                        inner=250).compile()
    assert "f64" in compiled.as_text()
    _assert_fits(compiled)


def test_pallas_minplus_1792(one_chip):
    """The Pallas (min,+) kernel at 1792 x 1792 compiles natively."""
    from repro.kernels.minplus import minplus
    a = jax.ShapeDtypeStruct((1792, 1792), jnp.float32, sharding=one_chip)
    compiled = jax.jit(partial(minplus, interpret=False)).lower(
        a, a).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_fits(compiled)
