"""Share of its roofline of one of the expert model's operations: the least
time the chip could take for what the run's counters say was needed
(benchmarks.flops_pangu_moe, the larger of operations over the bf16 peak
and bytes over the HBM peak), over the operation's device time in the
trace. The metric's file gives `cost` and how the operation is found:
`kernels` (a Pallas kernel's name, as readers/named_kernel_roofline.py) or
`patterns` (pieces of XLA's own instruction, as readers/kernel_roofline.py,
with `{E}` experts held, `{H}` hidden and `{M}` an expert's width). Nothing
where the trace has no such event or the program no such counter."""
from benchmarks import flops, flops_pangu_moe as F
from benchmarks import weights_pangu_moe as W
from benchmarks.readers.kernel_roofline import fill
from benchmarks.readers.named_kernel_roofline import named_seconds


def read(ctx, spec):
    tr = ctx["trace"]
    if tr is None or not tr.devices or ctx["peaks"] is None:
        return None
    c, config = ctx["rec"].counters, ctx["cell"].config
    if spec["cost"] == "latent_decode_cost":
        if not c.get("serve/decode_kv_rows"):
            return None
        layers = config["num_hidden_layers"]
        cost = F.latent_decode_cost(config, c["serve/decode_kv_rows"] * layers,
                                    c["serve/decode_steps"] * layers)
    elif spec["cost"] == "expert_product_cost":
        if not c.get("serve/expert_assignments_here"):
            return None
        cost = F.expert_product_cost(config, c["serve/experts_hit"],
                                     c["serve/expert_assignments_here"])
    else:
        raise KeyError(spec["cost"])
    if "kernels" in spec:
        seconds = named_seconds(tr.op_seconds(), spec["kernels"])
    else:
        s = W.sizes(config)
        seconds, _ = tr.seconds_matching(fill(
            spec["patterns"], {"E": s["E"], "H": s["H"], "M": s["M"]}))
    if not seconds:
        return None
    return 100.0 * flops.roofline_seconds(cost, ctx["peaks"])[0] / seconds
