"""Device time of the exchange between groups, in ms a round: the ops
under the ``exchange`` scope (encode, mix and decode of every stream),
averaged over the chips. On one chip it holds no collective: it is the
averaging's passes over the (G, N) buffers."""
import trace_scopes


def read(ctx):
    return trace_scopes.train_ms(ctx, "round", ("exchange",))
