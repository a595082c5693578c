"""100 x the device time of the operations whose instruction fits one of
the file's `patterns`, over the device's busy time. The patterns are pieces
of XLA's own instruction (benchmarks.trace.signature) with the cell's sizes
filled in (`fields`): `{B}` the engine's slots, `{MB}` a row's table of
pages, `{M}` the compressed keys a table holds, `{KC}` the width of a
page's compressed keys, `{G}` query heads a KV head, `{HKV}`, `{D}`,
`{LNH}`, `{LD}`. Nothing where the configuration has no such sizes or the
trace no such event."""
from benchmarks.readers.kernel_roofline import fill


def fields(cell) -> dict:
    c, e = cell.config, cell.settings["engine"]
    sp = c["assumed"]["sparse_config"]
    mb = -(-(int(e["prompt_cap"]) + int(e["max_new_tokens"]))
           // int(e["kv_block"]))
    r = sp["block_size"] // sp["kernel_stride"]
    hkv, d = c["num_key_value_heads"], c["head_dim"]
    return {"B": int(e["max_batch"]), "MB": mb, "M": mb * r,
            "KC": hkv * r * d, "G": c["num_attention_heads"] // hkv,
            "HKV": hkv, "D": d, "LNH": c["lightning_nh"],
            "LD": c["lightning_head_dim"]}


def read(ctx, spec):
    tr, cell = ctx["trace"], ctx["cell"]
    if tr is None or not tr.devices or not tr.busy_s \
            or "sparse_config" not in cell.config.get("assumed", {}):
        return None
    seconds, events = tr.seconds_matching(fill(spec["patterns"],
                                               fields(cell)))
    return 100.0 * seconds / tr.busy_s if events else None
