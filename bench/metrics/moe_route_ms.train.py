"""Device time of the expert layers' routing, in ms a local step: the
ops under ``local_steps``/``fwd_bwd``/``moe``/``router`` (the router's
product, softmax, top-k and load-balance loss) and ``moe``/``permute``
(the sort by expert, the gather into expert order, the scatter back and
the gated sum), forward, remat and backward, averaged over the chips."""
import trace_scopes

MOE = ("local_steps", "fwd_bwd", "moe")


def read(ctx):
    return trace_scopes.train_ms(ctx, "step", MOE + ("router",),
                                 MOE + ("permute",))
