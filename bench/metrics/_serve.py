"""What the serving readers share: device times from the trace, and
counts of the work in the measured window from the host's records."""
import flops

DECODE_PROGRAM = "decode_loop"
ATTN_KERNEL = "paged_attention"


def kernel_device(run):
    return run.trace.ops_matching(ATTN_KERNEL) if run.trace else (0.0, 0)


def prefill_flops(run):
    return sum(flops.prefill_flops(run.dims, run.recs[rid].prompt_len - 1)
               for w in run.windows for rid in w.admitted)


def decode_ctx_by_step(run):
    """Per fused decode step in the window: the kv lengths of its live
    lanes (each attends over its cache plus the new token)."""
    steps = []
    for w in run.windows:
        per = [[] for _ in range(w.steps)]
        for before, after in w.advance.values():
            for j in range(after - before):
                per[j].append(before + j + 1)
        steps.extend(per)
    return steps


def decode_step_ms(run):
    if not run.trace:
        return None
    s, _ = run.trace.modules_matching(DECODE_PROGRAM)
    steps = sum(w.steps for w in run.windows)
    return 1e3 * s / steps if s and steps else None


def window_model_flops(run):
    dec = sum(flops.decode_flops(run.dims, ctx)
              for ctx in decode_ctx_by_step(run))
    return prefill_flops(run) + dec
