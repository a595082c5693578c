"""Whole-step model FLOP/s utilization of a training cell: the operations
the forward and backward passes require per token (benchmarks.flops, no
recomputation counted) times the tokens per second of this run's own
window, over chips times the chip's bf16 peak."""
from benchmarks import flops, weights


def read(ctx, spec):
    if ctx["peaks"] is None:
        return None
    cell = ctx["cell"]
    config = cell.config
    rate = ctx["out"]["end_to_end"].get("train_tokens_per_s")
    if not rate:
        return None
    per_token = flops.train_flops_per_token(
        weights.n_params(config), config["num_layers"],
        config["hidden_size"], int(cell.traffic["seq"]))
    return 100.0 * per_token * rate / (
        cell.chips * ctx["peaks"]["bf16_flops"])
