"""The dense projection kernel's share of its roofline: reading each
client's gradient and LBG once (``counts.projection_bytes``) at the chip's
HBM bandwidth, over its measured device time."""
import counts
import trace_reduce

KERNEL = "lbgm_projection"


def read(ctx):
    s = trace_reduce.op_seconds(ctx["trace"], KERNEL) / ctx["rounds"]
    if s <= 0:
        return None
    k = round(ctx["fl"]["num_clients"] * ctx["fl"].get("sample_frac", 1.0))
    need = counts.projection_bytes(ctx["shapes"], k)
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / s
