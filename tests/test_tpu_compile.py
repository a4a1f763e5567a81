"""Compile the main path's kernels for a described TPU v5e chip, at real
widths, with no chip attached.

The TPU compiler refuses what interpret mode accepts: layouts Mosaic
cannot lower, blocks that overflow VMEM.  Each test lowers one kernel (or
the GP fit that differentiates through one) for a single v5e device and
checks the compiled program carries the Pallas kernel.  The topology is
described inside a module fixture, so only the worker that runs this file
loads the TPU library, and every worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attention as fa
from repro.kernels import gp_kernel
from repro.kernels import ops as kops
from repro.uq import gp


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **static):
    compiled = jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_gp_kernel_matrix_compiles(one_chip):
    x = _spec(one_chip, (2048, 7))
    _compile(gp_kernel.gp_kernel_matrix, x, x, _spec(one_chip, (7,)),
             _spec(one_chip, ()))


@pytest.mark.parametrize("n", [2048, 5000])
def test_gp_predict_compiles(one_chip, n):
    compiled = _compile(
        gp_kernel.gp_predict, _spec(one_chip, (n, 7)),
        _spec(one_chip, (1024, 7)), _spec(one_chip, (7,)),
        _spec(one_chip, ()), _spec(one_chip, (n, 2)),
        _spec(one_chip, (n, n)))
    # the L^-1 operand alone is n^2 f32 in HBM; nothing of that size may
    # be staged as a temporary
    assert compiled.memory_analysis().temp_size_in_bytes < n * n * 4


def test_gp_predict_experts_compiles(one_chip):
    e, n = 8, 512
    _compile(gp_kernel.gp_predict_experts, _spec(one_chip, (e, n, 7)),
             _spec(one_chip, (e, 1024, 7)), _spec(one_chip, (7,)),
             _spec(one_chip, ()), _spec(one_chip, (e, n, 2)),
             _spec(one_chip, (e, n, n)))


def test_flash_attention_compiles(one_chip):
    # configs/qwen3_14b.py: 40 query heads, 8 KV heads, head_dim 128
    q = _spec(one_chip, (1, 4096, 40, 128), jnp.bfloat16)
    kv = _spec(one_chip, (1, 4096, 8, 128), jnp.bfloat16)
    _compile(fa.flash_attention, q, kv, kv, causal=True)


def test_gp_fit_compiles_through_kernel(one_chip):
    """The NLML fit differentiates through the Pallas covariance (custom
    VJP); at the runtime predictor's training cap of 256 points."""
    kops.set_default_impl("pallas")
    try:
        compiled = gp._fit.lower(_spec(one_chip, (256, 7)),
                                 _spec(one_chip, (256, 1)), kind="rbf",
                                 steps=100, lr=5e-2).compile()
    finally:
        kops.set_default_impl("auto")
    assert "tpu_custom_call" in compiled.as_text()
