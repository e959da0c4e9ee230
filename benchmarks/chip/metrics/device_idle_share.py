"""Share of the traced window in which no operation ran on the device
(mean over the cell's chips): 1 - busy union / window."""
import trace_reduce


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - trace_reduce.busy_s(t) / t["window_s"])
