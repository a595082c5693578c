"""Whole-step model FLOP/s utilization of a serving cell of an expert model
held in shares (benchmarks.flops_pangu_moe): what the answered requests'
tokens, the assignments the held experts computed and the attended pairs
required, over the seconds from the window's opening to the last answer,
times the chip's bf16 peak. Nothing where the program has no expert
counters."""
from benchmarks import flops_pangu_moe as F


def read(ctx, spec):
    if ctx["peaks"] is None:
        return None
    c, config = ctx["rec"].counters, ctx["cell"].config
    if not c.get("serve/total_s") or \
            "serve/expert_assignments_here" not in c:
        return None
    need = F.forward_flops(
        config, c["serve/prompt_tokens_computed"] + c["serve/decode_steps"],
        c["serve/expert_assignments_here"],
        c["serve/prefill_pairs"] + c["serve/decode_kv_rows"])
    return 100.0 * need / (c["serve/total_s"] * ctx["cell"].chips
                           * ctx["peaks"]["bf16_flops"])
