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
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.base import ShapeConfig
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.jacobi.kernel import jacobi_step
from repro.kernels.paged_attention import ops as paged_ops
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
    the pool holding every lane's whole budget (the engine default), its
    rows lane-dense ``(block_size, KV*D)``."""
    lanes, heads, kv, d, max_seq = 8, 32, 8, 128, 1024
    max_blocks = max_seq // block_size
    pool = (lanes * max_blocks, block_size, kv * d)
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


# ------------------------------------------------ paged serving programs
# granite3b.batch's deployment: granite-3-2b whole, 16 lanes, max_seq
# 3072, a pool of 1536 blocks of 16
PAGED_LANES, PAGED_MAX_SEQ, PAGED_BS, PAGED_NB = 16, 3072, 16, 1536
_INSTR = re.compile(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* "
                    r"(copy|dynamic-slice|dynamic-update-slice)\(")


def _pool_moves(hlo: str, bs: int, kvd: int, layer_rows: int):
    """Copies and (update-)slices of pool rows covering a layer or more:
    what a layer scan does to a pool it does not carry in place."""
    moves = []
    for m in _INSTR.finditer(hlo):
        dims = [int(x) for x in m.group(1).split(",") if x]
        if dims[-2:] == [bs, kvd]:
            rows = 1
            for x in dims[:-2]:
                rows *= x
            if rows >= layer_rows:
                moves.append(m.group(0).strip())
    return moves


@pytest.fixture
def mosaic(monkeypatch):
    """Trace the paged kernel as on a TPU: the Mosaic kernel, not the
    CPU reference or interpret mode."""
    monkeypatch.setattr(paged_ops, "_on_tpu", lambda: True)
    paged_ops.paged_attention.clear_cache()
    yield
    paged_ops.paged_attention.clear_cache()


@pytest.mark.parametrize("program", ["decode_loop", "prefill_first",
                                     "prefill_history"])
def test_paged_programs_update_the_pool_in_place(one_chip, mosaic, program):
    """The paged decode window and both chunk-prefill programs at
    granite3b.batch's shapes: the layer scans carry the lane-dense pool
    and write it in place, so no op copies, slices or stacks a layer's
    pool or more, and the temporaries stay below one layer's pool."""
    cfg = get_config("granite-3-2b").with_(param_dtype="bfloat16")
    shape = ShapeConfig("serve", PAGED_MAX_SEQ, PAGED_LANES, "decode")

    def placed(tree):
        return jax.tree.map(
            lambda s: _sds(one_chip, s.shape, s.dtype), tree)

    params = placed(zoo.abstract_state(cfg).params)
    state = placed(zoo.abstract_paged_decode_state(cfg, shape, PAGED_BS,
                                                   PAGED_NB))
    if program == "decode_loop":
        fn = zoo.make_paged_decode_loop(cfg, shape, 8, PAGED_BS, PAGED_NB)
        args = (params, state,
                placed(jax.eval_shape(
                    lambda: zoo.init_sample_state(cfg, shape, seed=0))),
                _sds(one_chip, (PAGED_LANES, PAGED_MAX_SEQ), jnp.int32))
        donate = (1, 2)
    else:
        fn = zoo.make_paged_bulk_prefill(
            cfg, shape, 256, PAGED_BS, PAGED_NB,
            first_chunk=program == "prefill_first")
        scalar = _sds(one_chip, (), jnp.int32)
        args = (params, state, _sds(one_chip, (1, 256), jnp.int32),
                scalar, scalar, scalar)
        donate = (1,)
    compiled = jax.jit(fn, donate_argnums=donate).lower(*args).compile()
    hlo = compiled.as_text()
    pool = state.cache["k"]
    assert pool.shape == (cfg.num_layers, PAGED_NB, PAGED_BS,
                          cfg.num_kv_heads * cfg.head_dim)
    if program == "decode_loop":
        assert _has_kernel(compiled)
    assert re.search(r"= bf16\[%d,%d,%d\]\S* scatter\(" % (
        cfg.num_layers * PAGED_NB, PAGED_BS, pool.shape[-1]), hlo)
    assert _pool_moves(hlo, PAGED_BS, pool.shape[-1], PAGED_NB) == []
    layer_bytes = pool.size // cfg.num_layers * pool.dtype.itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
