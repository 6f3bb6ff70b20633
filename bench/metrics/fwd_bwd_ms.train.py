"""Device time of the model's forward, remat and backward, in ms a local
step: the ops under the ``local_steps``/``fwd_bwd`` scope
(``optim/packing.value_and_flat_grad``), averaged over the chips."""
import trace_scopes


def read(ctx):
    return trace_scopes.train_ms(ctx, "step", ("local_steps", "fwd_bwd"))
