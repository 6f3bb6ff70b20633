"""Device time of the optimizer update, in ms a local step: the ops under
``local_steps``/``opt_update`` (the fused update kernel and whatever XLA
scheduled in that scope for it), averaged over the chips."""
import trace_scopes


def read(ctx):
    return trace_scopes.train_ms(ctx, "step", ("local_steps", "opt_update"))
