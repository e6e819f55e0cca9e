"""Share of the roofline reached by the paged decode-attention kernels
(dense or quantized KV): least time (the context's K/V bytes and page
codebooks over HBM bandwidth, or q.k and p.v FLOPs over the bf16 peak)
over the summed device time of those kernels in the trace."""
from harness import trace, work

KERNELS = [r"_paged_attention_jit", r"_paged_attention_quant_jit"]


def read(run):
    seconds = trace.kernel_seconds(run.trace, KERNELS)
    flops, nbytes = work.paged_attention_work(run.spec, run.traced_steps())
    if nbytes <= 0:
        return None
    return work.share(work.least_time(flops, nbytes, run.peaks), seconds)
