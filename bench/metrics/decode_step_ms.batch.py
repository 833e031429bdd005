"""Device time of the fused decode programs per fused step (trace:
modules named ``jit_decode_loop``; steps from the host's step_many
calls in the traced window)."""
import _serve


def read(run):
    return _serve.decode_step_ms(run)
