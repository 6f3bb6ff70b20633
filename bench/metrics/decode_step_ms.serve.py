"""Mean fenced batched decode step (the engine's own ``decode_step``
phase, ``StepReport.decode_s``), in milliseconds."""


def read(ctx):
    if ctx.get("kind") != "serve" or ctx["decode_steps"] <= 0:
        return None
    return 1000.0 * ctx["decode_s"] / ctx["decode_steps"]
