"""Operations and bytes each timed kernel needs, from its shapes.

These are the yardstick's own counts: no compiler cost model (which costs
the body of a ``while`` loop once) and nothing the program reports.
"""

from __future__ import annotations


def dgemm_flops(n: int, m: int, k: int) -> float:
    """(n, k) x (k, m): one multiply and one add per term."""
    return 2.0 * n * m * k


def triad_length(n_bytes: int, itemsize: int = 4) -> int:
    """Vector length of a TRIAD working set of ``n_bytes`` over three
    arrays (the program's sizing rule)."""
    return max(1024, n_bytes // (3 * itemsize))


def triad_bytes(n_bytes: int, itemsize: int = 4) -> float:
    """Bytes one TRIAD call moves: read A, read B, write C."""
    return 3.0 * triad_length(n_bytes, itemsize) * itemsize


def flash_flops(b: int, h: int, s: int, d: int, causal: bool = True) -> float:
    """One attention forward over (B, H, S, D): Q K^T and P V. Causal
    counts the half of the score matrix on and below the diagonal."""
    full = 2.0 * b * h * (2.0 * s * s * d)
    return full / 2.0 if causal else full


def flash_bytes(b: int, h: int, h_kv: int, s: int, d: int,
                itemsize: int = 2) -> float:
    """Bytes one attention forward must move at least: read Q and write O
    over the query heads, read K and V over the key/value heads."""
    return float(itemsize) * b * s * d * (2 * h + 2 * h_kv)


def attention_matmul_params(model: dict) -> int:
    d, h, hk = model["d_model"], model["n_heads"], model["n_kv_heads"]
    dh = model.get("head_dim") or d // h
    return d * h * dh + 2 * d * hk * dh + h * dh * d


def mlp_matmul_params(model: dict) -> int:
    n_mats = 3 if model.get("mlp_type", "glu") == "glu" else 2
    return n_mats * model["d_model"] * model["d_ff"]


def train_step_flops(model: dict, batch: int, seq: int) -> float:
    """FLOPs one training step (forward and backward) requires.

    Backward is twice the forward. Matmul parameters count every layer's
    projections and the LM head over the published vocabulary; the
    embedding lookup is free. Attention is causal: half of Q K^T and P V.
    Recomputation under remat does not count.
    """
    layers = model["n_layers"]
    d, h = model["d_model"], model["n_heads"]
    dh = model.get("head_dim") or d // h
    params = (layers * (attention_matmul_params(model)
                        + mlp_matmul_params(model))
              + d * model["vocab_size"])
    tokens = batch * seq
    forward = (2.0 * params * tokens
               + layers * flash_flops(batch, h, seq, dh, causal=True))
    return 3.0 * forward
