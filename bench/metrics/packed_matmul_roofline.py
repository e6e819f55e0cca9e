"""Share of the roofline reached by the packed codebook matmuls and the
packed tied head: least time (the larger of model FLOPs over the bf16
peak and eq.-14 bytes over HBM bandwidth, for the tokens each step
served) over the summed device time of those kernels in the trace."""
from harness import trace, work

# Device ops of these kernels: the custom call takes the name of the jit
# around the Pallas call (``%_packed_codebook_matmul_jit.47 = ...``),
# matched whole against the op name (regular expressions).
KERNELS = [r"_packed_codebook_matmul_jit", r"_packed_codebook_matmul_t_jit"]


def read(run):
    seconds = trace.kernel_seconds(run.trace, KERNELS)
    flops, nbytes = work.packed_work(run.spec, run.traced_steps())
    if nbytes <= 0:
        return None
    return work.share(work.least_time(flops, nbytes, run.peaks), seconds)
