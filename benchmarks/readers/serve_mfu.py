"""Whole-step model FLOP/s utilization of a serving cell: the forward
operations the answered requests required (2 per parameter for every prompt
token not served from the prefix cache and every output token, plus
attention over the context each attended to), over the seconds from the
window's opening to the last answer, times the chip's bf16 peak. Padding to
the prefill shape and recomputed or discarded tokens do not count."""
from benchmarks import flops, weights


def read(ctx, spec):
    if ctx["peaks"] is None:
        return None
    c, config = ctx["rec"].counters, ctx["cell"].config
    if not c.get("serve/total_s") or "serve/output_tokens" not in c:
        return None
    need = flops.forward_flops(
        weights.n_params(config), config["num_layers"],
        config["hidden_size"],
        c["serve/prompt_tokens_computed"] + c["serve/decode_steps"],
        c["serve/prefill_pairs"] + c["serve/decode_kv_rows"])
    return 100.0 * need / (c["serve/total_s"] * ctx["cell"].chips
                           * ctx["peaks"]["bf16_flops"])
