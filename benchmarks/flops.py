"""Operations and bytes the algorithm needs, from shapes alone. These are
the numerators of every share of a peak or of a roofline the benchmark
reports; the program is not asked.

Training (copied from bench.py's `flops_per_token = 6*n_params +
12*L*H*S`, the PaLM appendix-B count): 2 FLOPs per parameter per token in
the forward pass and 4 in the backward, plus attention's two S x S products
per layer (2*2*S*H forward per token, uncounted causal saving, x3 with the
backward). Recomputed operations do not count.
"""
from __future__ import annotations


def train_flops_per_token(n_params: int, layers: int, hidden: int,
                          seq: int) -> float:
    return 6.0 * n_params + 12.0 * layers * hidden * seq


def forward_flops(n_params: int, layers: int, hidden: int,
                  new_tokens: int, context_tokens: int) -> float:
    """Forward pass over `new_tokens` positions that between them attend to
    `context_tokens` (query, key) pairs: 2 per parameter per token, and
    4*H per pair per layer (QK^T and PV, 2 FLOPs a multiply-add)."""
    return 2.0 * n_params * new_tokens + 4.0 * layers * hidden * context_tokens


def flash_attention_cost(batch: int, seq: int, heads: int, head_dim: int,
                         itemsize: int = 2, backward: bool = False) -> dict:
    """Causal flash attention on [B, S, nh, hd]. Forward: QK^T and PV over
    the causal half, 2 * 2*S*S*hd/2 per head. Backward (dq and dkv
    kernels together): five S x S products (QK^T again, dP, dV, dQ, dK)
    over the causal half. Bytes: q, k, v read and o written once forward;
    q, k, v, o, do read and dq, dk, dv written backward."""
    pairs = batch * heads * seq * seq / 2.0
    if not backward:
        return {"flops": 4.0 * pairs * head_dim,
                "bytes": 4.0 * batch * seq * heads * head_dim * itemsize}
    return {"flops": 10.0 * pairs * head_dim,
            "bytes": 8.0 * batch * seq * heads * head_dim * itemsize}


def linear_ce_cost(tokens: int, hidden: int, vocab: int,
                   itemsize: int = 2, backward: bool = False) -> dict:
    """Fused output projection and cross-entropy on x [T, H], W [V, H].
    Forward: one T x V x H product. Backward: the logits again, then dx
    and dW: three products. Bytes: x and W read once (and written once as
    gradients in the backward); the [T, V] logits never reach HBM."""
    prod = 2.0 * tokens * hidden * vocab
    io = (tokens * hidden + vocab * hidden) * itemsize
    if not backward:
        return {"flops": prod, "bytes": io}
    return {"flops": 3.0 * prod, "bytes": 2.0 * io}


def paged_attention_cost(kv_rows_read: int, queries: int, heads: int,
                         head_dim: int, itemsize: int = 2) -> dict:
    """Decode attention over a block pool: each query row reads its own K
    and V rows once (`kv_rows_read` summed over the batch and the calls)
    and does one QK^T and one PV product per row read."""
    return {"flops": 4.0 * kv_rows_read * heads * head_dim,
            "bytes": (2.0 * kv_rows_read + 2.0 * queries)
            * heads * head_dim * itemsize}


def roofline_seconds(cost: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which roof gives it."""
    t_flops = cost["flops"] / peaks["bf16_flops"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")
