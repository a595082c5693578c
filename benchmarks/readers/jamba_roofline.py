"""Device time of one of the Jamba model's own operations, found by
`kernels` (a Pallas kernel's name, as readers/named_kernel_roofline.py),
by `patterns` (pieces of XLA's own instruction, as
readers/kernel_roofline.py, with the cell's sizes filled in: `{B}` the
engine's slots, `{N}` d_state, `{DIN}` d_inner, `{CONV}` the conv state's
width), or both. With `cost` (a function of benchmarks.flops_jamba) the
metric is the share of its roofline: the least time the chip could take
for what the run's counters say was needed (the larger of operations over
the bf16 peak and bytes over the HBM peak), over that device time. Without,
it is 100 x that device time over the device's busy time. Nothing where
the trace has no such event or the program no such counter."""
from benchmarks import flops, flops_jamba as F
from benchmarks.readers.kernel_roofline import fill
from benchmarks.readers.named_kernel_roofline import named_seconds


def fields(cell) -> dict:
    c = cell.config
    din = c["mamba_expand"] * c["hidden_size"]
    return {"B": int(cell.settings["engine"]["max_batch"]),
            "N": c["mamba_d_state"], "DIN": din,
            "CONV": (c["mamba_d_conv"] - 1) * din}


def _cost(spec, c, cell):
    config = cell.config
    if spec["cost"] == "scan_cost":
        if not c.get("serve/ssm_tokens_scanned"):
            return None
        return F.scan_cost(config, c["serve/ssm_tokens_scanned"],
                           c["serve/ssm_windows_scanned"])
    if not c.get("serve/ssm_rows_updated"):
        return None
    if spec["cost"] == "update_cost":
        return F.update_cost(config, c["serve/ssm_rows_updated"])
    if spec["cost"] == "mqa_decode_cost":
        return F.mqa_decode_cost(
            config, c["serve/attn_pages_walked"],
            F.attention_row_steps(config, c["serve/ssm_rows_updated"]),
            int(cell.settings["engine"]["kv_block"]))
    raise KeyError(spec["cost"])


def read(ctx, spec):
    tr, cell = ctx["trace"], ctx["cell"]
    if tr is None or not tr.devices or "mamba_d_state" not in cell.config:
        return None
    seconds = 0.0
    if "kernels" in spec:
        seconds += named_seconds(tr.op_seconds(), spec["kernels"])
    if "patterns" in spec:
        seconds += tr.seconds_matching(
            fill(spec["patterns"], fields(cell)))[0]
    if not seconds:
        return None
    if "cost" not in spec:
        return 100.0 * seconds / tr.busy_s if tr.busy_s else None
    if ctx["peaks"] is None:
        return None
    cost = _cost(spec, ctx["rec"].counters, cell)
    if cost is None:
        return None
    return 100.0 * flops.roofline_seconds(cost, ctx["peaks"])[0] / seconds
