"""Operations and bytes the openPangu-Ultra-MoE share needs, from shapes and
the run's counters alone (the numerators of `serve_step_mfu.moe`,
`latent_decode_roofline.moe` and `expert_product_roofline.moe`).

A token costs 2 FLOPs for every parameter outside the routed experts
(without the embedding, which is looked up); an assignment computed here
costs one expert, 2 FLOPs a parameter; an attended (query, latent) pair
costs, in every layer, the score and the context products of the absorbed
form over all heads: 2 nh (W + rank), W = rank + rope.
"""
from __future__ import annotations

from benchmarks import weights_pangu_moe as W


def _pair_flops(c: dict) -> float:
    return 2.0 * c["nh"] * (2 * c["Rkv"] + c["dr"])


def forward_flops(config: dict, tokens: float, assignments_here: float,
                  pairs: float) -> float:
    c, n = W.sizes(config), W.n_params(config)
    dense = n["outside_experts"] - c["V"] * c["H"]
    return (2.0 * dense * tokens + 2.0 * n["one_expert"] * assignments_here
            + _pair_flops(c) * c["L"] * pairs)


def latent_decode_cost(config: dict, rows_read: float, queries: float,
                       itemsize: int = 2) -> dict:
    """Decode attention over the latent pool: each query row reads its own
    latents once a layer (`rows_read`, summed over batch, steps and
    layers) and does the score and context products with every head;
    `queries` rows of q [nh, W] in and of context [nh, rank] out."""
    c = W.sizes(config)
    w = c["Rkv"] + c["dr"]
    return {"flops": _pair_flops(c) * rows_read,
            "bytes": (rows_read * w + queries * c["nh"] * (w + c["Rkv"]))
            * itemsize}


def expert_product_cost(config: dict, experts_hit: float,
                        assignments_here: float, itemsize: int = 2) -> dict:
    """The routed experts' products: every expert hit streams its three
    matrices once a call; every assignment reads a token's activations and
    writes its share of the result, and costs one expert's FLOPs."""
    c, n = W.sizes(config), W.n_params(config)
    return {"flops": 2.0 * n["one_expert"] * assignments_here,
            "bytes": (experts_hit * n["one_expert"]
                      + assignments_here * 2 * c["H"]) * itemsize}
