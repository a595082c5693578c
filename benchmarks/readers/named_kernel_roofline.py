"""A kernel's share of its roofline, the kernel found by the name the
program gives it (`pl.pallas_call(..., name=...)`): the least time the
chip could take for the calls the window made, over the device time of
the events whose instruction is a custom call named after the kernel.

A device event is named by its whole HLO instruction, and the kernel's
name stands in the instruction's own name wrapped by the transforms it
ran under: `%pallas_flash_fwd.7`, `%transpose_jvp_pallas_flash_dq__.3`.
So the name is looked for there, between a `%` or `_` and the
underscores and `.<n>` that end it; `pallas_paged_decode` is not found in
`%pallas_paged_decode_q8.1`. Device time is the benchmark's own-time rule
(`TraceSummary.op_seconds`).

The metric's file gives `calls`: a list of {kernels, cost, ...}. For
`flash_attention_cost` and `linear_ce_cost` a call also gives `backward`
and `per_step` ("layers" or a number) and the least time comes from the
cell's shapes and the steps taken, as in readers/kernel_roofline.py; for
`paged_attention_cost` it comes from the KV rows the answered requests'
decode steps had to read, as in readers/paged_roofline.py. The costs are
benchmarks.flops's: one yardstick, two ways of finding the kernel. A
call none of whose kernels has an event gives nothing."""
import re

from benchmarks import flops


def holds(event: str, kernel: str) -> bool:
    """The event is a custom call whose own name holds the kernel's."""
    lhs, _, rhs = event.partition(" = ")
    return " custom-call(" in rhs and re.search(
        r"(?:^|[%_])" + re.escape(kernel) + r"_*(?:\.|$)", lhs) is not None


def named_seconds(op_seconds: dict, kernels) -> float:
    """Device seconds of the named kernels, out of `TraceSummary.
    op_seconds()` (seconds by whole instruction)."""
    return sum(sec for event, sec in op_seconds.items()
               if any(holds(event, k) for k in kernels))


def _least_seconds(ctx, call) -> float | None:
    cell, counters = ctx["cell"], ctx["rec"].counters
    c, t = cell.config, cell.traffic
    if call["cost"] == "paged_attention_cost":
        if not counters.get("serve/decode_kv_rows"):
            return None
        cost = flops.paged_attention_cost(
            counters["serve/decode_kv_rows"] * c["num_layers"],
            counters["serve/decode_steps"] * c["num_layers"],
            c["num_heads"], c["head_dim"])
        return flops.roofline_seconds(cost, ctx["peaks"])[0]
    steps = counters.get("train/steps")
    if not steps:
        return None
    back = bool(call["backward"])
    if call["cost"] == "flash_attention_cost":
        cost = flops.flash_attention_cost(
            batch=int(t["batch"]), seq=int(t["seq"]), heads=c["num_heads"],
            head_dim=c["head_dim"], backward=back)
    elif call["cost"] == "linear_ce_cost":
        cost = flops.linear_ce_cost(
            tokens=int(t["batch"]) * int(t["seq"]), hidden=c["hidden_size"],
            vocab=c["vocab_size"], backward=back)
    else:
        raise KeyError(call["cost"])
    per_step = c["num_layers"] if call["per_step"] == "layers" \
        else float(call["per_step"])
    least = flops.roofline_seconds(cost, ctx["peaks"])[0]
    return least / cell.chips * per_step * steps


def read(ctx, spec):
    tr = ctx["trace"]
    if tr is None or not tr.devices or ctx["peaks"] is None:
        return None
    ideal, measured, ops = 0.0, 0.0, tr.op_seconds()
    for call in spec["calls"]:
        seconds = named_seconds(ops, call["kernels"])
        least = _least_seconds(ctx, call)
        if not seconds or least is None:
            return None
        ideal += least
        measured += seconds
    return 100.0 * ideal / measured
