"""The sparse decision kernel's share of its roofline: the least time of
its required HBM traffic (``counts.decision_bytes``) at the chip's HBM
bandwidth over its measured device time. Memory-bound: it does no
arithmetic the MXU has to do."""
import counts
import trace_reduce

KERNEL = "lbgm_sparse_decision"


def read(ctx):
    s = trace_reduce.op_seconds(ctx["trace"], KERNEL) / ctx["rounds"]
    if s <= 0:
        return None
    k = round(ctx["fl"]["num_clients"] * ctx["fl"].get("sample_frac", 1.0))
    need = counts.decision_bytes(ctx["shapes"],
                                 ctx["fl"]["lbg_kw"]["k_frac"], k)
    return 100.0 * need / ctx["peak"]["hbm_bytes_per_s"] / s
