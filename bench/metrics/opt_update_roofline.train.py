"""Share of the HBM roofline reached by the optimizer update: the bytes
the heavy-ball update must move (``counts.momentum_update_bytes`` of the
chip's G_local x N_padded elements, each local step of each traced
round) over the device time of the ops that implement it, at the chip's
HBM bandwidth. The update is bound by bytes (0.25 FLOP per byte).

The update is matched by what it computes, from the op's HLO text: the
Pallas TPU kernel that reads three (G_local, N_padded) float32 buffers
(params, gradient, momentum) and writes two (params, momentum). Where a
later program runs the update as something else, nothing is matched and
the metric is left out."""
import counts
import trace_reduce



def patterns(g: int, n: int):
    buf = rf"f32\[{g},{n}\]"
    return [rf"^%\S+ = \({buf}\S*, {buf}\S*\) custom-call\("
            rf"{buf}\S* %\S+, {buf}\S* %\S+, {buf}\S* %\S+\)"
            rf".*tpu_custom_call"]


def read(ctx):
    if ctx.get("kind") != "train":
        return None
    secs = trace_reduce.match_seconds(
        ctx["reduced"], patterns(ctx["groups_per_chip"], ctx["n_padded"]))
    if secs <= 0:
        return None
    steps = ctx["rounds"] * ctx["traffic"]["local_steps"]
    need = counts.momentum_update_bytes(
        ctx["groups_per_chip"] * ctx["n_padded"]) * steps
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / secs
