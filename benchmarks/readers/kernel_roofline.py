"""A kernel's share of its roofline: the least time the chip could take
for the calls the window made (the larger of operations over the bf16 peak
and bytes over the HBM peak, from benchmarks.flops on the cell's shapes),
over the kernel's device time in the trace.

The metric's file gives `calls`: a list of {patterns, cost, backward,
per_step}; `patterns` are pieces of the kernel's signature in the trace
(benchmarks.trace.signature) with the cell's sizes as `{B}`, `{S}`, `{T}`
(B x S), `{H}`, `{V}`, `{NH}`, `{BH}` (B x heads) and `{D}` (the head size
padded to 128 lanes); `cost` is a function of benchmarks.flops, and
`per_step` how many such calls one training step needs ("layers" or a
number). A kernel whose events are not in the trace gives nothing."""
from benchmarks import flops


def fields(cell) -> dict:
    c, t = cell.config, cell.traffic
    b, s = int(t["batch"]), int(t["seq"])
    return {"B": b, "S": s, "T": b * s, "H": c["hidden_size"],
            "V": c["vocab_size"], "NH": c["num_heads"],
            "BH": b * c["num_heads"], "D": -(-c["head_dim"] // 128) * 128}


def fill(patterns, f: dict) -> list:
    return [[part.format(**f) for part in
             ([p] if isinstance(p, str) else p)] for p in patterns]


def _shape_args(cell, cost: str, backward: bool) -> tuple[dict, int]:
    c, t = cell.config, cell.traffic
    shard = cell.chips
    if cost == "flash_attention_cost":
        return dict(batch=int(t["batch"]), seq=int(t["seq"]),
                    heads=c["num_heads"], head_dim=c["head_dim"],
                    backward=backward), shard
    if cost == "linear_ce_cost":
        return dict(tokens=int(t["batch"]) * int(t["seq"]),
                    hidden=c["hidden_size"], vocab=c["vocab_size"],
                    backward=backward), shard
    raise KeyError(cost)


def read(ctx, spec):
    tr, cell = ctx["trace"], ctx["cell"]
    if tr is None or not tr.devices or ctx["peaks"] is None:
        return None
    steps = ctx["rec"].counters.get("train/steps")
    if not steps:
        return None
    ideal, measured = 0.0, 0.0
    f = fields(cell)
    for call in spec["calls"]:
        seconds, events = tr.seconds_matching(fill(call["patterns"], f))
        if not events:
            return None
        kw, shard = _shape_args(cell, call["cost"], bool(call["backward"]))
        cost = getattr(flops, call["cost"])(**kw)
        least, _ = flops.roofline_seconds(cost, ctx["peaks"])
        per_step = cell.config["num_layers"] \
            if call["per_step"] == "layers" else float(call["per_step"])
        ideal += least / shard * per_step * steps
        measured += seconds
    return 100.0 * ideal / measured if measured else None
