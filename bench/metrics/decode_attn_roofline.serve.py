"""Share of its roofline reached by the paged decode-attention kernel:
the least time the chip could take for the window's decode attention —
the larger of its required bytes (every resident token's K and V rows in
the float32 pool, each slot's query and output) over HBM bandwidth and
its required FLOPs over the bf16 peak — over the kernel's device time.
The bytes bound it (about one FLOP per byte)."""
import counts
import trace_reduce



def patterns(ctx):
    """The kernel, matched by what it computes from the op's HLO text: a
    Pallas TPU call whose output is the slots' attention, float32
    (slots, KV heads, query heads per KV head, head size)."""
    a = ctx["arch"]
    kv, hd = a["n_kv_heads"], counts.head_dim(a)
    shape = f"{ctx['traffic']['engine']['n_slots']},{kv}," \
            f"{a['n_heads'] // kv},{hd}"
    return [rf"^%\S+ = f32\[{shape}\]\S* custom-call\(.*tpu_custom_call"]


def read(ctx):
    if ctx.get("kind") != "serve" or not ctx["contexts"]:
        return None
    secs = trace_reduce.match_seconds(ctx["reduced"], patterns(ctx))
    if secs <= 0:
        return None
    arch, pk = ctx["arch"], ctx["peaks"]
    need = max(counts.decode_attn_bytes(arch, ctx["contexts"])
               / pk["hbm_bytes_per_s"],
               counts.decode_attn_flops(arch, ctx["contexts"])
               / pk["bf16_flops_per_s"])
    return 100.0 * need / secs
