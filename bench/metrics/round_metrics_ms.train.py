"""Device time of the round telemetry, in ms a round: the ops under the
``round_metrics`` scope (consensus distance before and after the
exchange, codec error mass), averaged over the chips."""
import trace_scopes


def read(ctx):
    return trace_scopes.train_ms(ctx, "round", ("round_metrics",))
