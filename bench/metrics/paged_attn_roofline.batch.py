"""Paged-attention kernel time in the trace against the least time the
chip could take for the same calls: per fused step and layer, the
larger of operations / peak FLOP/s and bytes / HBM bandwidth, with
operations and bytes reckoned from the live lanes' kv lengths only
(``flops.paged_attention_cost``), never from capacity or blocks
fetched.  In percent."""
import _serve
import flops


def read(run):
    t, _ = _serve.kernel_device(run)
    if not t:
        return None
    need = 0.0
    for ctx in _serve.decode_ctx_by_step(run):
        if ctx:
            f, b = flops.paged_attention_cost(run.dims, ctx)
            need += run.dims.layers * flops.roofline_seconds(f, b, run.peak)
    return 100.0 * need / t
