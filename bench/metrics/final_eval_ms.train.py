"""Device time of the loss and gradient evaluated at each round's result,
in ms a round: the ops under the ``final_eval`` scope, averaged over the
chips."""
import trace_scopes


def read(ctx):
    return trace_scopes.train_ms(ctx, "round", ("final_eval",))
