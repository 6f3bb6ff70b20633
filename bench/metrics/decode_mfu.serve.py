"""Model FLOP/s utilization of the decode steps: the FLOPs they require
(2 N per active slot plus attention over its resident tokens,
``counts.decode_flops``) over the summed fenced decode-step time at the
chip's bf16 peak."""
import counts


def read(ctx):
    if ctx.get("kind") != "serve" or ctx["decode_s"] <= 0:
        return None
    flops = counts.decode_flops(ctx["arch"], ctx["contexts"])
    return 100.0 * flops / (ctx["decode_s"]
                            * ctx["peaks"]["bf16_flops_per_s"])
