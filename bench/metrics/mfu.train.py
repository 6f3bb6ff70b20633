"""Model FLOP/s utilization of training: the FLOPs the trained tokens
require (``counts.train_flops_per_token``) times the traced window's
tokens per second per chip, over the chip's bf16 peak."""
import counts


def read(ctx):
    if ctx.get("kind") != "train" or ctx["tokens_per_s_per_chip"] <= 0:
        return None
    per_token = counts.train_flops_per_token(ctx["arch"],
                                             ctx["traffic"]["seq_len"])
    return (100.0 * per_token * ctx["tokens_per_s_per_chip"]
            / ctx["peaks"]["bf16_flops_per_s"])
