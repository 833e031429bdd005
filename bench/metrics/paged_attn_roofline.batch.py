"""Paged-attention kernel time in the trace against the least time the
chip could take for the same calls: per fused step, the larger of
operations / peak FLOP/s and bytes / HBM bandwidth of one call, times
the model's paged-kernel calls a step (``attention_layers``), with
operations and bytes reckoned from the live lanes' kv lengths only
(the model module's ``paged_attention_cost``), never from capacity or
blocks fetched.  In percent."""
import _serve
import flops


def read(run):
    t, _ = _serve.kernel_device(run)
    if not t:
        return None
    calls = run.model.attention_layers(run.dims)
    need = 0.0
    for ctx in _serve.decode_ctx_by_step(run):
        if ctx:
            f, b = run.model.paged_attention_cost(run.dims, ctx)
            need += calls * flops.roofline_seconds(f, b, run.peak)
    return 100.0 * need / t
