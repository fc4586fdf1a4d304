"""The main path's kernels compile for a TPU v5e, with no chip attached.

The TPU compiler is installed here; ``get_topology_desc`` describes a
v5e:2x2 host and each test lowers + compiles one kernel at its real
width (``interpret=False``, ``step_block(W, False)``) for one of its
chips. This catches what interpret mode cannot (tiling, VMEM limits,
lowering rules) at no chip time. The topology is described only inside
the ``topo`` fixture — never at import — so every xdist worker collects
the same tests and only the worker given this file loads the TPU
library. Keep these tests in this one file.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _bitset_args(one_chip, n_keys, n, W, S):
    from jepsen_tpu.checker import wgl_bitset as bs

    return (
        _spec((n_keys, n * 4 * W), jnp.int8, one_chip),
        _spec((n_keys, n * bs.META_COLS), jnp.int32, one_chip),
        _spec((n_keys, S, bs.bitset_words(W)), jnp.int32, one_chip),
    )


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("W,S,exact", [
    (12, 8, False),   # the north star's narrow bucket, fast tier
    (16, 32, True),   # widest bucket below the 17-19 compile cliff,
                      # exact tier (the fast tier compiles in the chain)
])
def test_bitset_scan_compiles(one_chip, W, S, exact):
    from jepsen_tpu.checker import wgl_bitset as bs

    n = 64 * bs.step_block(W, False)
    compiled = bs._bitset_scan.lower(
        *_bitset_args(one_chip, 1, n, W, S),
        model_name="cas-register", S=S, W=W, interpret=False,
        exact=exact,
    ).compile()
    _assert_kernel(compiled)


def test_donated_chain_compiles(one_chip):
    """The resident twin at a two-segment plan (W12 -> W16), frontier
    donated."""
    from jepsen_tpu.checker import wgl_bitset as bs

    S = 8
    args = []
    for W in (12, 16):
        win, meta, _ = _bitset_args(
            one_chip, 1, 32 * bs.step_block(W, False), W, S
        )
        args += [win, meta]
    fr0 = _spec((1, S, bs.bitset_words(12)), jnp.int32, one_chip)
    compiled = bs._chain_scan_donated.lower(
        tuple(args), fr0, seg_ws=(12, 16), model_name="cas-register",
        S=S, interpret=False, exact=False,
    ).compile()
    _assert_kernel(compiled)


def test_pallas_k_scan_compiles(one_chip):
    from jepsen_tpu.checker import wgl_pallas as wp

    K, W, n = 128, 16, 64 * wp.STEP_BLOCK
    compiled = wp._pallas_scan.lower(
        _spec((1, n, 4, W), jnp.int32, one_chip),
        _spec((1, n, 1, wp.META_COLS), jnp.int32, one_chip),
        model_name="cas-register", K=K, W=W, interpret=False,
    ).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("N,B", [(16, 1024), (256, 8)])
def test_graph_kernel_compiles(one_chip, N, B):
    """Both sides of packed_word_max_n: word-packed OR-gather (N <= 32)
    and the dense f32 matmul closure."""
    from jepsen_tpu.checker import txn_graph as tg

    packed_max = tg._packed_word_max_n()
    assert (N <= packed_max) == (N == 16)
    fn = tg._graph_kernel(tg._n_iters(N), True, True, packed_max)
    compiled = fn.lower(
        _spec((B, N, N), jnp.float32, one_chip),
        _spec((B, N, N), jnp.float32, one_chip),
        _spec((B, N, N), jnp.bool_, one_chip),
    ).compile()
    assert compiled.as_text()


def test_longfork_kernel_compiles(one_chip):
    from jepsen_tpu.checker.longfork import _fork_kernel

    G, R, n = 256, 256, 2048
    compiled = _fork_kernel().lower(
        _spec((G, R, n), jnp.float32, one_chip),
        _spec((G, R), jnp.bool_, one_chip),
    ).compile()
    mem = compiled.memory_analysis()
    assert mem is None or np.isfinite(mem.temp_size_in_bytes)
