"""jit'd paged-attention entry point (decode layout: one token per lane).

``impl`` dispatch mirrors ``kernels.flash_attention.ops``:

* ``"ref"``    — gather-through-the-block-table + ``full_attention``;
  *bit-identical* to the dense decode path (the serving engine's paged
  mode uses this on CPU backends so paged and dense engines emit the
  same token streams).
* ``"kernel"`` — the Pallas kernel (interpret-mode off TPU), validated
  against the ref in tests.
* ``"auto"``   — kernel on TPU, ref elsewhere (interpret-mode Pallas in
  the fused decode hot loop would be pure overhead on CPU).
"""
import functools

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import paged_attention as \
    _paged_kernel
from repro.kernels.paged_attention.ref import gather_pages, \
    paged_attention_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("impl",))
def paged_attention(q, k_pool, v_pool, block_tables, kv_len, *,
                    impl: str = "auto"):
    """q: (B, H, D); pools: (num_rows, bs, KV*D) lane-dense rows;
    block_tables: (B, max_blocks) int32 pool rows (sentinel entries
    allowed — clamped here); kv_len: (B,) int32.  Returns (B, H, D)."""
    if impl == "auto":
        impl = "kernel" if _on_tpu() else "ref"
    if impl == "ref":
        return paged_attention_ref(q, k_pool, v_pool, block_tables, kv_len)
    n_rows = k_pool.shape[0]
    bt = jnp.clip(block_tables, 0, n_rows - 1)
    return _paged_kernel(q, k_pool, v_pool, bt, kv_len,
                         interpret=not _on_tpu())
