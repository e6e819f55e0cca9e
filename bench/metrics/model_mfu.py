"""Model FLOPs of every token computed in the traced window (prefill and
decode: 2 per matmul weight, the head where logits are taken, attention
over each token's context) over the window times the chip's bf16 peak."""
from harness import trace, work


def read(run):
    flops = work.model_flops(run.spec, run.traced_steps())
    window = trace.busy(run.trace)["window_s"]
    if flops <= 0 or window <= 0:
        return None
    return 100.0 * flops / (window * run.peaks["bf16_flops_per_s"])
