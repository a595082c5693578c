"""Whole-step model FLOP/s utilization of a serving cell of the
MiniCPM-SALA model (benchmarks.flops_minicpm_sala): what the answered
requests' tokens, the pairs the sparse layers attended, the compressed keys
they scored and the recurrent layers' state required, over the seconds
from the window's opening to the last answer, times the chip's bf16 peak.
Nothing where the program has no such counters."""
from benchmarks import flops_minicpm_sala as F


def read(ctx, spec):
    if ctx["peaks"] is None:
        return None
    c, config = ctx["rec"].counters, ctx["cell"].config
    if not c.get("serve/total_s") or "serve/attn_pairs" not in c:
        return None
    need = F.forward_flops(
        config, c["serve/prompt_tokens_computed"] + c["serve/decode_steps"],
        c["serve/attn_pairs"], c["serve/sparse_keys_scored"])
    return 100.0 * need / (c["serve/total_s"] * ctx["cell"].chips
                           * ctx["peaks"]["bf16_flops"])
