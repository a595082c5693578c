"""The paged decode-attention kernel's share of its roofline: the KV rows
the answered requests' decode steps had to read (each step reads its row's
whole context once, in every layer), as bytes over the HBM peak or
operations over the bf16 peak, whichever is larger, over the kernel's
device time in the trace (`patterns`: pieces of its signature, with the
engine's sizes as `{MB}` max_batch, `{KB}` kv_blocks, `{BS}` kv_block,
`{NH}` heads, `{D}` the head size padded to 128 lanes).
Counts cover the window and the wait for late answers, as the trace does
not: so it is read only where the trace spans both (`needs_full_trace`)."""
from benchmarks import flops
from benchmarks.readers.kernel_roofline import fill


def read(ctx, spec):
    tr = ctx["trace"]
    if tr is None or not tr.devices or ctx["peaks"] is None:
        return None
    c, config = ctx["rec"].counters, ctx["cell"].config
    eng = ctx["cell"].settings["engine"]
    f = {"MB": eng["max_batch"], "KB": eng["kv_blocks"],
         "BS": eng["kv_block"], "NH": config["num_heads"],
         "D": -(-config["head_dim"] // 128) * 128}
    seconds, events = tr.seconds_matching(fill(spec["patterns"], f))
    if not events or not c.get("serve/decode_kv_rows"):
        return None
    cost = flops.paged_attention_cost(
        c["serve/decode_kv_rows"] * config["num_layers"],
        c["serve/decode_steps"] * config["num_layers"],
        config["num_heads"], config["head_dim"])
    least, _ = flops.roofline_seconds(cost, ctx["peaks"])
    return 100.0 * least / seconds
