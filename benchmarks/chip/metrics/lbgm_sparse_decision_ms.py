"""Device time of the sparse decision kernel per round: the summed
durations of the ``lbgm_sparse_decision`` ops in the traced window over
the rounds traced (mean over chips)."""
import trace_reduce

KERNEL = "lbgm_sparse_decision"


def read(ctx):
    s = trace_reduce.op_seconds(ctx["trace"], KERNEL)
    return 1e3 * s / ctx["rounds"] if s > 0 else None
