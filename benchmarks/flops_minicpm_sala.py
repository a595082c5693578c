"""Operations and bytes a MiniCPM-SALA cut needs, from shapes and the run's
counters alone (the numerators of `serve_step_mfu.sala`,
`sparse_decode_roofline.sala` and `state_update_roofline.sala`). Needed
work only: what a kernel reads besides (a page's unattended tail, a done
row's state) is not counted.

A token costs 2 FLOPs for every parameter it is multiplied with (all but
the embedding, which is looked up) and, in every `lightning-attn` layer,
the state's update and its read: 4 nh d^2 (k v^T into S, S^T q). An
attended (query, token) pair of a `minicpm4` layer costs the score and the
value product over all query heads, 4 nh hd; a compressed key a selecting
query scored costs its scores, 2 nh hd.
"""
from __future__ import annotations

from benchmarks import weights_minicpm_sala as W


def forward_flops(config: dict, tokens: float, pairs: float,
                  keys_scored: float) -> float:
    """`pairs` and `keys_scored` are summed over the `minicpm4` layers (the
    program's `attn_pairs` and `sparse_keys_scored`)."""
    c, n = W.sizes(config), W.n_params(config)
    state_layers = sum(m == W.LIGHTNING for m in c["mixers"])
    return (2.0 * n["multiplied"] * tokens
            + 4.0 * c["lnh"] * c["lhd"] ** 2 * state_layers * tokens
            + 4.0 * c["nh"] * c["hd"] * pairs
            + 2.0 * c["nh"] * c["hd"] * keys_scored)


def sparse_decode_cost(config: dict, pages: float, row_steps: float,
                       itemsize: int = 2) -> dict:
    """The page-walking decode kernel of the `minicpm4` layers: `pages`
    (page, KV head) pairs walked, each a page of keys and one of values
    read once and worked by the group's query heads; `row_steps` (row,
    layer) steps, each all query heads of q in and of the context out."""
    c = W.sizes(config)
    g = c["nh"] // c["nkv"]
    return {"flops": 4.0 * g * c["hd"] * c["block"] * pages,
            "bytes": (2.0 * c["block"] * c["hd"] * pages
                      + 2.0 * c["nh"] * c["hd"] * row_steps) * itemsize}


def state_update_cost(config: dict, rows: float) -> dict:
    """The decode step of the `lightning-attn` layers: `rows` (row, layer)
    updates, each its float32 state read and written once."""
    c = W.sizes(config)
    size = c["lnh"] * c["lhd"] ** 2
    return {"flops": 4.0 * size * rows, "bytes": 2.0 * 4 * size * rows}
