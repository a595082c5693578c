"""Open-loop chat traffic (after inference.serving.shared_prefix_traffic,
copied here and extended with per-request output lengths): independent
users behind a handful of system prompts. Each request is one of
`n_system` system prompts of `system_len` tokens plus a Pareto-tailed user
part, and asks for a log-uniform number of output tokens.

Every seed gets the same requests and the same gaps between them, in
another order: the sequence (user length, output length, system prompt,
gap to the next arrival) is drawn once from `population_seed` in the
traffic file (n = rate x seconds requests, exponential gaps scaled to fill
the window exactly), and the run's seed chooses where in that cycle the
window starts and draws every token id. So two seeds offer the same work
with the same bursts and differ in which of them the window opens on and
in what the prompts say.
"""
from __future__ import annotations

import numpy as np


def population(params: dict, seconds: float) -> dict:
    """The cycle of requests every seed rotates: sizes, systems, gaps."""
    p = params
    n = max(2, int(round(float(p["rate"]) * seconds)))
    pop = np.random.default_rng([int(p["population_seed"]), n])
    user = p["user_len_min"] * (1.0 + pop.pareto(p["user_len_alpha"], n))
    user = np.clip(user, p["user_len_min"], p["user_len_max"]).astype(int)
    out = np.exp(pop.uniform(np.log(p["out_len_min"]),
                             np.log(p["out_len_max"] + 1), n)).astype(int)
    out = np.clip(out, p["out_len_min"], p["out_len_max"])
    gaps = pop.exponential(1.0, n)
    gaps *= seconds / np.sum(gaps)
    which = pop.integers(0, int(p["n_system"]), n)
    return {"user": user, "out": out, "gaps": gaps, "which": which}


def make(params: dict, config: dict, seed: int, seconds: float) -> dict:
    p = params
    pop = population(p, seconds)
    n = len(pop["user"])
    rng = np.random.default_rng([int(seed), 0x63686174])
    shift = int(rng.integers(0, n))
    user, out, gaps, which = (np.roll(pop[k], -shift)
                              for k in ("user", "out", "gaps", "which"))
    at = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    vocab = int(p.get("real_vocab", config["vocab_size"]))
    systems = np.random.default_rng([int(seed), 0x737973]).integers(
        1, vocab, (int(p["n_system"]), int(p["system_len"])))
    reqs = []
    for i in range(n):
        prompt = np.concatenate([
            systems[which[i]], rng.integers(1, vocab, int(user[i]))])
        reqs.append({"at": float(at[i]), "prompt": prompt.astype(np.int64),
                     "max_new_tokens": int(out[i]),
                     "system": int(which[i])})
    return {"requests": reqs, "systems": systems.astype(np.int64)}
