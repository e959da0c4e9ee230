"""Device time of the dense projection kernel per round: the summed
durations of the ``lbgm_projection`` ops in the traced window over the
rounds traced (mean over chips)."""
import trace_reduce

KERNEL = "lbgm_projection"


def read(ctx):
    s = trace_reduce.op_seconds(ctx["trace"], KERNEL)
    return 1e3 * s / ctx["rounds"] if s > 0 else None
