"""Share of its roofline reached by the expert layers' grouped matrix
products: the least time they can take a local step
(``moe_counts.grouped_mm_seconds`` of each group's tokens, times the
chip's groups) over their device time, the ops under
``local_steps``/``fwd_bwd``/``moe``/``experts`` (forward, remat and
backward). The remat's recomputed forward is in the time and not in the
least time, so the share stays under 100%."""
import moe_counts
import trace_scopes


def read(ctx):
    arch = ctx.get("arch") or {}
    if ctx.get("kind") != "train" or not arch.get("n_experts"):
        return None
    ms = trace_scopes.train_ms(ctx, "step",
                               ("local_steps", "fwd_bwd", "moe", "experts"))
    if ms is None:
        return None
    tf = ctx["traffic"]
    need = ctx["groups_per_chip"] * moe_counts.grouped_mm_seconds(
        arch, tf["per_group_batch"] * tf["seq_len"], ctx["peaks"])
    return 100.0 * 1000.0 * need / ms
