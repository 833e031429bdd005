"""Share of the fused decode programs' device op time that carries no
model scope (trace: ops of modules named ``jit_decode_loop``, each
mapped through the engine's ``program_hlo()`` to the innermost of the
model module's ``SCOPES`` in its instruction's ``op_name``), over all
their op time.  In percent.  Unscoped are ops traced outside every
scope, as layout copies and the scan's own slicing are, and ops the
scope map lacks, so that a map that fails to match the trace reads
high rather than clean."""
import _serve
import trace_reduce


def read(run):
    by = _serve.scope_seconds(run, _serve.DECODE_PROGRAM)
    total = sum(by.values())
    unscoped = by.get(trace_reduce.UNSCOPED, 0.0) + by.get(
        trace_reduce.UNMAPPED, 0.0)
    return 100.0 * unscoped / total if total else None
