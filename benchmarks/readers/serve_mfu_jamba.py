"""Whole-step model FLOP/s utilization of a serving cell of the Jamba
model (benchmarks.flops_jamba): what the answered requests' tokens, the
pairs the attention layers attended and the Mamba layers' scan required,
over the seconds from the window's opening to the last answer, times the
chip's bf16 peak. Nothing where the program has no such counters."""
from benchmarks import flops_jamba as F


def read(ctx, spec):
    if ctx["peaks"] is None:
        return None
    c, config = ctx["rec"].counters, ctx["cell"].config
    if not c.get("serve/total_s") or "serve/ssm_rows_updated" not in c:
        return None
    need = F.forward_flops(
        config, c["serve/prompt_tokens_computed"] + c["serve/decode_steps"],
        c["serve/attn_pairs"])
    return 100.0 * need / (c["serve/total_s"] * ctx["cell"].chips
                           * ctx["peaks"]["bf16_flops"])
