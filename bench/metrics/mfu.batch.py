"""Whole-step model FLOP/s utilization: operations of every token the
window processed (prompt tokens prefilled, decode tokens with their
logits) over (the window's host-clock length x the chip's peak), in
percent."""
import _serve


def read(run):
    return 100.0 * _serve.window_model_flops(run) / (
        run.wall_s * run.peak["bf16_flops_per_s"])
