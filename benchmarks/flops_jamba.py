"""Operations and bytes a Jamba configuration needs, from shapes and the
run's counters alone (the numerators of `serve_step_mfu.jamba`,
`ssm_scan_roofline.jamba`, `ssm_update_roofline.jamba` and
`mqa_decode_roofline.jamba`). Needed work only: what a kernel reads or
computes besides (a window's padding tokens, a done row's state, a page's
unattended tail) is not counted.

A token costs 2 FLOPs for every parameter it is multiplied with (the
matrices; the tied embedding once, as the head) and, in every Mamba layer,
the scan: 9 a (channel, state) pair (dt A, the exp, times S, dt x, times B,
the add, times C, the sum over n, and D x amortised), d_inner x d_state of
them. That is VECTOR-unit work: no matmul unit sees it, and the bf16 peak
it is divided by is not its roof. An attended (query, token) pair of an
attention layer costs the score and the value product over all query
heads, 4 nh hd.
"""
from __future__ import annotations

from benchmarks import weights_jamba as W

SCAN_FLOPS = 9.0


def _mamba_layers(c: dict) -> int:
    return sum(m == W.MAMBA for m in c["mixers"])


def forward_flops(config: dict, tokens: float, pairs: float) -> float:
    """`pairs` is summed over the attention layers (the program's
    `attn_pairs`)."""
    c, n = W.sizes(config), W.n_params(config)
    return (2.0 * n["multiplied"] * tokens
            + SCAN_FLOPS * c["Din"] * c["N"] * _mamba_layers(c) * tokens
            + 4.0 * c["nh"] * c["hd"] * pairs)


def scan_cost(config: dict, tokens: float, windows: float) -> dict:
    """The selective-scan kernel over prefill windows: `tokens` live
    (token, layer) pairs, each x and dt in and y out (float32 [d_inner])
    and B and C in (float32 [d_state]); `windows` (window, layer) pairs,
    each the state [d_state, d_inner] float32 read and written once."""
    c = W.sizes(config)
    return {"flops": SCAN_FLOPS * c["Din"] * c["N"] * tokens,
            "bytes": 4.0 * ((3 * c["Din"] + 2 * c["N"]) * tokens
                            + 2 * c["N"] * c["Din"] * windows)}


def state_row_bytes(config: dict) -> int:
    """One row of one Mamba layer's two state arrays, float32: the scan's
    [d_state, d_inner] and the convolution's [(d_conv - 1) d_inner]."""
    c = W.sizes(config)
    return 4 * c["Din"] * (c["N"] + c["K"] - 1)


def update_cost(config: dict, rows: float) -> dict:
    """The decode step of the Mamba layers: `rows` LIVE (row, layer)
    updates, each both state arrays read and written once."""
    c = W.sizes(config)
    return {"flops": SCAN_FLOPS * c["Din"] * c["N"] * rows,
            "bytes": 2.0 * state_row_bytes(config) * rows}


def mqa_decode_cost(config: dict, pages: float, row_steps: float,
                    block: int, itemsize: int = 2) -> dict:
    """The page-walking decode kernel of the attention layers
    (flops_minicpm_sala.sparse_decode_cost's form): `pages` (page, KV
    head) pairs walked, each a page of keys and one of values read once and
    worked by the group's query heads; `row_steps` (row, layer) steps, each
    all query heads of q in and of the context out."""
    c = W.sizes(config)
    g = c["nh"] // c["nkv"]
    return {"flops": 4.0 * g * c["hd"] * block * pages,
            "bytes": (2.0 * block * c["hd"] * pages
                      + 2.0 * c["nh"] * c["hd"] * row_steps) * itemsize}


def attention_row_steps(config: dict, ssm_rows_updated: float) -> float:
    """(row, attention layer) decode steps, from the Mamba layers' count
    of the same live rows."""
    c = W.sizes(config)
    n = _mamba_layers(c)
    return ssm_rows_updated * (c["L"] - n) / n
