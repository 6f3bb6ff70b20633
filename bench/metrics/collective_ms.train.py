"""Device time of the collectives (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all) per round, averaged over
the chips: the exchange between groups on different chips."""


def read(ctx):
    if ctx.get("kind") != "train" or ctx["reduced"]["n_devices"] < 2:
        return None
    secs = ctx["reduced"]["collective_s"]
    if secs <= 0 or ctx["rounds"] <= 0:
        return None
    return 1000.0 * secs / ctx["rounds"]
