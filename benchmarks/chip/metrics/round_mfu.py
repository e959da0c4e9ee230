"""The round's model FLOP utilisation: the clients' local-SGD forward and
backward FLOPs per round (``counts.round_model_flops``) over the traced
round time times chips times the chip's bf16 peak (a DEFAULT-precision
float32 matmul runs on the bf16 MXU)."""
import counts


def read(ctx):
    flops = counts.round_model_flops(ctx["model"], ctx["cfg"], ctx["fl"],
                                     ctx["traffic"])
    peak = ctx["peak"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * flops / (ctx["round_s"] * peak)
