"""Ahead-of-time compiles for a described TPU v5e, at real widths.

Interpret mode accepts block shapes and VMEM budgets that the chip's
compiler refuses, so every Pallas kernel and one decode window of the
serving path are compiled here against a ``v5e:2x2`` topology that is
described, not attached.  Nothing runs: these tests catch what the TPU
compiler would reject (tiling, VMEM, HBM fit) before any chip time is
spent.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and under pytest-xdist every worker imports every test file, so the
worker given this file must be the only one that loads it.  Keep all such
compiles in this one file for the same reason.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.jacobi.kernel import jacobi_step
from repro.kernels.paged_attention.kernel import paged_attention
from repro.kernels.ssd.kernel import ssd_intra_chunk
from repro.models import model_zoo as zoo

HBM_BYTES = 16 * 1024 ** 3          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache here; keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


# ----------------------------------------------------------- paged decode
@pytest.mark.parametrize("block_size", [8, 16, 128])
def test_paged_kernel_compiles_at_granite_decode_width(one_chip, block_size):
    """granite-8b decode: 8 lanes, 32 q / 8 kv heads of 128, max_seq 1024,
    the pool holding every lane's whole budget (the engine default)."""
    lanes, heads, kv, d, max_seq = 8, 32, 8, 128, 1024
    max_blocks = max_seq // block_size
    pool = (lanes * max_blocks, block_size, kv, d)
    fn = jax.jit(functools.partial(paged_attention, interpret=False))
    compiled = fn.lower(
        _sds(one_chip, (lanes, heads, d), jnp.bfloat16),
        _sds(one_chip, pool, jnp.bfloat16),
        _sds(one_chip, pool, jnp.bfloat16),
        _sds(one_chip, (lanes, max_blocks), jnp.int32),
        _sds(one_chip, (lanes,), jnp.int32)).compile()
    assert _has_kernel(compiled)


# ---------------------------------------------------------------- flash
def test_flash_kernel_compiles_at_4k(one_chip):
    b, h, kv, s, d = 1, 32, 8, 4096, 128
    fn = jax.jit(functools.partial(flash_attention, causal=True))
    compiled = fn.lower(_sds(one_chip, (b, h, s, d), jnp.bfloat16),
                        _sds(one_chip, (b, kv, s, d), jnp.bfloat16),
                        _sds(one_chip, (b, kv, s, d), jnp.bfloat16)).compile()
    assert _has_kernel(compiled)


# ------------------------------------------------------------------ ssd
def test_ssd_kernel_compiles_at_mamba2_780m_width(one_chip):
    cfg = get_config("mamba2-780m")
    b, nc, l = 1, 8, cfg.ssm_chunk
    h, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    f32 = jnp.float32
    compiled = jax.jit(ssd_intra_chunk).lower(
        _sds(one_chip, (b, nc, l, h, p), f32),
        _sds(one_chip, (b, nc, l, h), f32),
        _sds(one_chip, (b, nc, l, h), f32),
        _sds(one_chip, (b, nc, l, n), f32),
        _sds(one_chip, (b, nc, l, n), f32)).compile()
    assert _has_kernel(compiled)


# --------------------------------------------------------------- jacobi
def test_jacobi_kernel_compiles_at_4096_squared(one_chip):
    compiled = jax.jit(jacobi_step).lower(
        _sds(one_chip, (4096, 4096), jnp.float32)).compile()
    assert _has_kernel(compiled)


# ------------------------------------------------------ dense decode window
def test_dense_decode_window_compiles_at_granite_width(one_chip):
    """One fused decode window (``make_decode_loop``) of granite-8b at its
    published widths, cut to 2 layers, bf16 params, 8 lanes x 1024."""
    cfg = get_config("granite-8b").with_(num_layers=2,
                                         param_dtype="bfloat16")
    shape = ShapeConfig("serve", 1024, 8, "decode")
    loop = zoo.make_decode_loop(cfg, shape, n_steps=8)

    def placed(tree):
        return jax.tree.map(
            lambda s: _sds(one_chip, s.shape, s.dtype), tree)

    args = (
        placed(zoo.abstract_state(cfg).params),
        placed(zoo.abstract_decode_state(cfg, shape)),
        placed(jax.eval_shape(
            lambda: zoo.init_sample_state(cfg, shape, seed=0))),
        _sds(one_chip, (shape.global_batch, shape.seq_len), jnp.int32))
    compiled = jax.jit(loop, donate_argnums=(1, 2)).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used < HBM_BYTES, used
