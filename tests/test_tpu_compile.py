"""Compile rehearsal for a TPU v5e chip that is described, not attached.

The fabric's Pallas kernels and the network's step program are compiled
by the TPU compiler at the widths of the BSS-2 wafer module
(``configs/bss2.py``: 46 simulated chips, 512 neurons, 256 inputs, E=512,
fanout 4, bucket capacity 32, ring depth 32).  Interpret-mode tests on the
CPU cannot show what Mosaic refuses (unaligned blocks, primitives it does
not lower, int32 matmuls); these compiles can, without a chip.  Nothing
runs, so results are pinned elsewhere (the interpret-mode tests, and
``chip_smoke.py`` on the chip).

The topology is described inside a module-scoped fixture: only the
worker that runs this file loads the TPU compiler library.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.bss2 import CONFIG
from repro.kernels import common
from repro.kernels.bucket_pack.kernel import bucket_pack_pallas
from repro.kernels.fused_drain.kernel import fused_drain_pallas
from repro.kernels.fused_inject.kernel import fused_inject_pallas
from repro.kernels.merge_sort.kernel import merge_sort_words_pallas
from repro.snn import network as net

COMM = dataclasses.replace(CONFIG.comm, superstep=4)
N_CHIPS = COMM.n_chips
E = COMM.event_capacity
LANES_IN = COMM.lanes_in                    # 46 * 32 = 1472 delivered lanes
LANES_PAD = LANES_IN + (-LANES_IN) % 128    # fused_drain's 128-lane padding
SORT_N = 2048                               # next power of two >= LANES_IN


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile written to the persistent cache cannot be read back
    # without a chip; keep this module's compiles out of it.
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_bucket_pack_compiles(one_chip):
    e = E * COMM.fanout                     # routed lanes of one substep
    kernel = functools.partial(bucket_pack_pallas, n_buckets=N_CHIPS,
                               capacity=COMM.bucket_capacity,
                               interpret=False)
    _assert_kernel(_compile(kernel, _spec(one_chip, (e,)),
                            _spec(one_chip, (e,))))


def test_merge_sort_words_compiles(one_chip):
    kernel = functools.partial(merge_sort_words_pallas, interpret=False)
    _assert_kernel(_compile(kernel, _spec(one_chip, (SORT_N,)),
                            _spec(one_chip, (SORT_N,))))


@pytest.mark.parametrize("B", [1, 4])
def test_fused_inject_compiles(one_chip, B):
    kernel = functools.partial(
        fused_inject_pallas, n_real=COMM.neurons_per_chip, n_chips=N_CHIPS,
        buckets_per_chip=COMM.buckets_per_chip,
        capacity=COMM.bucket_capacity, mode="simplified",
        time_window=COMM.time_window, interpret=False)
    s = functools.partial(_spec, one_chip)
    _assert_kernel(_compile(
        kernel, s((B, E)), s((B, E)), s((B, E)),
        s((COMM.neurons_per_chip, 4)), s((1, N_CHIPS)), s((1, 1))))


@pytest.mark.parametrize("mode,lanes,qdepth,rate", [
    ("passthrough", LANES_PAD, 8, 0),
    ("sort", SORT_N, 8, 0),
    ("rate", LANES_PAD, 256, 8),
])
def test_fused_drain_compiles(one_chip, mode, lanes, qdepth, rate):
    kernel = functools.partial(fused_drain_pallas, mode=mode, rate=rate,
                               extra_ahead=0, interpret=False)
    s = functools.partial(_spec, one_chip)
    _assert_kernel(_compile(
        kernel, s((COMM.superstep, lanes)), s((1, qdepth)),
        s((COMM.ring_depth, COMM.n_inputs_per_chip)), s((1, 1)),
        s((1, 1))))


def _run_block_program(sharding, comm):
    """net.run over one B-step block of the 46-chip network, compiled."""
    cfg = net.NetworkConfig(comm=comm, neuron_model=CONFIG.neuron_model)

    def shapes():
        params = net.init_params(jax.random.PRNGKey(0), cfg)
        return params, net.init_state(cfg, params)

    on_chip = lambda tree: jax.tree.map(
        lambda x: _spec(sharding, x.shape, x.dtype), tree)
    params, state = on_chip(jax.eval_shape(shapes))
    ext = _spec(sharding, (comm.superstep, N_CHIPS, comm.n_inputs_per_chip),
                jnp.float32)
    return _compile(functools.partial(net.run, cfg), params, state, ext)


def test_network_block_compiles_jnp(one_chip):
    compiled = _run_block_program(one_chip, COMM)
    assert "tpu_custom_call" not in compiled.as_text()


@pytest.mark.parametrize("fanout", [1, COMM.fanout])
def test_network_block_compiles_pallas(one_chip, monkeypatch, fanout):
    """The kernels as the fabric calls them: vmapped over 46 chips (fanout
    1 takes the fused inject, fanout 4 ``bucket_pack``).  The wrappers
    compile for the backend they find, so the test points them at the
    TPU."""
    monkeypatch.setattr(common, "on_tpu", lambda: True)
    comm = dataclasses.replace(COMM, fanout=fanout, use_pallas=True)
    _assert_kernel(_run_block_program(one_chip, comm))
