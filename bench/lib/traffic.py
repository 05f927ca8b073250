"""Training batches from a traffic file and a seed.

A copy of the program's seeded LM token generator (a bigram-structured
Markov stream, split into one shard per agent; each step draws every
agent's rows from its own shard), kept here so that the traffic cannot
change under a later PR.  The traffic file gives the sizes:

    {"agents": 2, "batch_per_agent": 1, "seq": 2048, "stream_tokens": 32768}

The same seed gives the same batches in the same order.
"""

from __future__ import annotations

from typing import Dict, Iterator

import numpy as np


def token_stream(n_tokens: int, vocab: int, rng: np.random.Generator,
                 prefer: int = 8, stay: float = 0.85) -> np.ndarray:
    """Each token prefers ``prefer`` successors; with probability
    ``1 - stay`` the next token is drawn uniformly instead."""
    prefs = rng.integers(0, vocab, size=(vocab, prefer))
    out = np.empty(n_tokens, dtype=np.int32)
    t = int(rng.integers(0, vocab))
    for i in range(n_tokens):
        out[i] = t
        if rng.random() < stay:
            t = int(prefs[t, rng.integers(0, prefer)])
        else:
            t = int(rng.integers(0, vocab))
    return out


def agent_batches(traffic: dict, vocab: int,
                  seed: int) -> Iterator[Dict[str, np.ndarray]]:
    """Endless ``{"inputs", "targets"}`` of shape ``(agents, batch, seq)``."""
    agents = int(traffic["agents"])
    batch = int(traffic["batch_per_agent"])
    seq = int(traffic["seq"])
    tokens = token_stream(int(traffic["stream_tokens"]), vocab,
                          np.random.default_rng([seed, 0]))
    shards = np.array_split(tokens, agents)
    if min(s.shape[0] for s in shards) <= seq + 1:
        raise ValueError(f"stream_tokens {traffic['stream_tokens']} leaves "
                         f"a shard shorter than seq + 1 = {seq + 1}")
    rng = np.random.default_rng([seed, 1])
    while True:
        inp, tgt = [], []
        for sh in shards:
            starts = rng.integers(0, sh.shape[0] - seq - 1, size=batch)
            inp.append(np.stack([sh[s:s + seq] for s in starts]))
            tgt.append(np.stack([sh[s + 1:s + seq + 1] for s in starts]))
        yield {"inputs": np.stack(inp), "targets": np.stack(tgt)}


def tokens_per_step(traffic: dict) -> int:
    return (int(traffic["agents"]) * int(traffic["batch_per_agent"])
            * int(traffic["seq"]))


def ring_pi(n: int) -> np.ndarray:
    """Metropolis-Hastings weights of a ring of ``n`` agents (a ring of 2
    is one edge): ``pi[j, l] = 1 / (1 + max(deg j, deg l))`` on each edge,
    the rest of each row on the diagonal."""
    adj = np.zeros((n, n))
    for j in range(n):
        for l in ((j + 1) % n, (j - 1) % n):
            if l != j:
                adj[j, l] = 1.0
    deg = adj.sum(1)
    pi = np.where(adj > 0, 1.0 / (1.0 + np.maximum(deg[:, None], deg[None])),
                  0.0)
    pi[np.diag_indices(n)] = 1.0 - pi.sum(1)
    return pi


TOPOLOGIES = {"ring": ring_pi}
