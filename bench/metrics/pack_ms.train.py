"""Device time of the flat buffer's two passes, in ms a local step: the
ops under ``local_steps``/``unpack`` (the leaves materialized from the
packed params) and ``local_steps``/``grad_pack`` (the gradient tree
packed into the flat buffer), averaged over the chips."""
import trace_scopes


def read(ctx):
    return trace_scopes.train_ms(ctx, "step", ("local_steps", "unpack"),
                                 ("local_steps", "grad_pack"))
