"""What the serving readers share: device times from the trace, and
counts of the work in the measured window from the host's records.
Operations and bytes come from the run's model module (``run.model``,
``bench/models/<model>.py``), never from a model's code directly."""
import trace_reduce

DECODE_PROGRAM = "decode_loop"
ATTN_KERNEL = "paged_attention"


def kernel_device(run):
    return run.trace.ops_matching(ATTN_KERNEL) if run.trace else (0.0, 0)


def scope_seconds(run, program):
    """{model scope: device op seconds} in the traced window, over the
    programs whose module name holds ``program``: the scopes are those
    the run's model module declares (``SCOPES``), ``none`` ops traced
    outside every one, ``unmapped`` instructions the scope map lacks;
    empty where the run has no trace or scopes."""
    if not (run.trace and run.scopes):
        return {}
    return trace_reduce.scope_seconds(run.trace.instr_s, run.scopes, program)


def prefill_flops(run):
    return sum(run.model.prefill_flops(run.dims,
                                       run.recs[rid].prompt_len - 1)
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
    dec = sum(run.model.decode_flops(run.dims, ctx)
              for ctx in decode_ctx_by_step(run))
    return prefill_flops(run) + dec
