"""Work the algorithm needs, counted from a configuration's published
sizes, whatever implements it.

``model_flops_per_token``: a dense encoder's training FLOPs per token,
6 x the parameters of its matrix products (the four attention
projections and the two MLP matrices of every layer, and the untied
output head over the unpadded vocabulary; the embedding lookup and the
norm scales do no matrix product) plus the attention scores and their
weighted sum, 2 x 2 x seq x d per token per layer forward and twice that
backward, over the full, non-causal seq x seq square.  Recomputation in
the backward pass is not counted.

``onebit_adam_least_bytes``: the HBM bytes one compressed 1-bit Adam
step needs per worker.  Per parameter it reads x, g, m, v and the worker
error and writes x, m and the worker error, all float32 (32 bytes).
Every byte of a 1-bit payload (a bit per element and a float32 scale per
block) is written once and read once: the worker's payload of the whole
vector, the received chunks (one payload of the whole vector in all),
the server's payload of its chunk, and the gathered payload of the whole
vector.  The server reads and writes its error over its chunk.
"""
from __future__ import annotations


def matmul_params(c: dict) -> int:
    d, f, L, v = (c["hidden_size"], c["intermediate_size"],
                  c["num_hidden_layers"], c["vocab_size"])
    return L * (4 * d * d + 2 * d * f) + d * v


def param_count(c: dict) -> int:
    """Trained parameters over the unpadded vocabulary: the matrices, the
    embedding and the norm scales (two per layer and a final one)."""
    d, L, v = c["hidden_size"], c["num_hidden_layers"], c["vocab_size"]
    return matmul_params(c) + v * d + (2 * L + 1) * d


def model_flops_per_token(c: dict, seq: int) -> float:
    return (6.0 * matmul_params(c)
            + 12.0 * c["num_hidden_layers"] * seq * c["hidden_size"])


def payload_bytes(n: float, block: int) -> float:
    """1-bit payload of ``n`` elements: a bit each, a float32 per block."""
    return n / 8 + 4 * n / block


def onebit_adam_least_bytes(n_params: int, workers: int, block: int) -> float:
    d, n = float(n_params), workers
    return (32 * d + 3 * payload_bytes(d, block) + 8 * d / n
            + payload_bytes(d / n, block))
