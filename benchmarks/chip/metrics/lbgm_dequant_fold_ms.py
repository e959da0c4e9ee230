"""Device time of the quantized fold kernel per round: the summed
durations of the ``lbgm_dequant_fold`` ops in the traced window over
the rounds traced (mean over chips). None where the program has no such
kernel."""
import trace_reduce

KERNEL = "lbgm_dequant_fold"


def read(ctx):
    s = trace_reduce.op_seconds(ctx["trace"], KERNEL)
    return 1e3 * s / ctx["rounds"] if s > 0 else None
