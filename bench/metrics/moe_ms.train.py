"""Device time of the expert layers, in ms a local step: the ops under
``local_steps``/``fwd_bwd``/``moe`` (router, permutation and grouped
products, forward, remat and backward, all layers), averaged over the
chips. A model without experts has no such scope and reads nothing."""
import trace_scopes


def read(ctx):
    return trace_scopes.train_ms(ctx, "step",
                                 ("local_steps", "fwd_bwd", "moe"))
