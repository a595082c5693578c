"""Share of its roofline of one of the MiniCPM-SALA model's decode
operations: the least time the chip could take for what the run's counters
say was needed (benchmarks.flops_minicpm_sala, the larger of operations
over the bf16 peak and bytes over the HBM peak), over the operation's
device time in the trace. The metric's file gives `cost` and how the
operation is found: `kernels` (a Pallas kernel's name, as
readers/named_kernel_roofline.py) or `patterns` (pieces of XLA's own
instruction, as readers/kernel_roofline.py, filled from
readers/pattern_device_share.fields). Nothing where the trace has no such
event or the program no such counter."""
from benchmarks import flops, flops_minicpm_sala as F
from benchmarks.readers.kernel_roofline import fill
from benchmarks.readers.named_kernel_roofline import named_seconds
from benchmarks.readers.pattern_device_share import fields


def read(ctx, spec):
    tr = ctx["trace"]
    if tr is None or not tr.devices or ctx["peaks"] is None:
        return None
    c, config = ctx["rec"].counters, ctx["cell"].config
    if spec["cost"] == "sparse_decode_cost":
        steps = c.get("serve/sparse_rows", 0) + c.get("serve/dense_rows", 0)
        if not steps:
            return None
        cost = F.sparse_decode_cost(
            config, c["serve/sparse_blocks_attended"]
            + c["serve/dense_blocks_attended"], steps)
    elif spec["cost"] == "state_update_cost":
        if not c.get("serve/state_rows_updated"):
            return None
        cost = F.state_update_cost(config, c["serve/state_rows_updated"])
    else:
        raise KeyError(spec["cost"])
    if "kernels" in spec:
        seconds = named_seconds(tr.op_seconds(), spec["kernels"])
    else:
        seconds, _ = tr.seconds_matching(
            fill(spec["patterns"], fields(ctx["cell"])))
    if not seconds:
        return None
    return 100.0 * flops.roofline_seconds(cost, ctx["peaks"])[0] / seconds
