"""Mean fenced prefill time per admission (the engine's own ``prefill``
phase, ``StepReport.prefill_s``), in milliseconds."""


def read(ctx):
    if ctx.get("kind") != "serve" or ctx["admitted"] <= 0:
        return None
    return 1000.0 * ctx["prefill_s"] / ctx["admitted"]
