"""Operations and bytes of the granite-4.0-h training step and of its
Pallas SSD kernel, from the shapes alone: the yardstick's own counts, like
``perfbench.counts``, with nothing taken from the program or a compiler.

Masked Q x Q terms count their useful half, as ``counts.flash_flops``
does for causal attention. Recomputation under remat does not count.
"""

from __future__ import annotations

from perfbench import counts

#: the chunk at which the step's required work is counted: the published
#: ``mamba_chunk_size``, whatever chunk a session tunes
STEP_CHUNK = 256


def layer_kinds(model: dict) -> list[str]:
    return list(model["layer_types"][:model["n_layers"]])


def ssm_heads(model: dict) -> int:
    return model["ssm_expand"] * model["d_model"] // model["ssm_head_dim"]


def mamba_matmul_params(model: dict) -> int:
    """z, x, B, C and dt projections in, the output projection out."""
    d = model["d_model"]
    inner = model["ssm_expand"] * d
    return d * (2 * inner + 2 * model["ssm_state"] + ssm_heads(model)) \
        + inner * d


def ssd_flops(b: int, s: int, h: int, p: int, n: int, q: int) -> float:
    """One forward of the chunked SSD scan with one group of B and C. Per
    chunk, C B^T once (shared by the heads), its causal half; per head and
    chunk, the causal half of the masked (Q x Q)(Q x P) product, C times
    the carried (P, N) state, and the state's update (Q P N each)."""
    per_chunk = 2.0 * q * q * n / 2.0
    per_head = 2.0 * q * q * p / 2.0 + 2.0 * q * n * p + 2.0 * q * p * n
    return b * (s // q) * (per_chunk + h * per_head)


def ssd_bytes(b: int, s: int, h: int, p: int, n: int,
              itemsize: int = 4) -> float:
    """Bytes one call of the kernel must move at least (its inputs are
    float32): read x dt and write y over the heads, read B and C once,
    read the cumulative decay of each head."""
    return float(itemsize) * b * s * (2 * h * p + 2 * n + h)


def train_step_flops(model: dict, batch: int, seq: int) -> float:
    """FLOPs one training step (forward and backward) requires: three
    times the forward. The forward counts every layer's projections and
    MLP, the LM head over the published vocabulary (the embedding lookup
    is free), causal attention, and the SSD scan at ``STEP_CHUNK``."""
    kinds = layer_kinds(model)
    mamba, attention = kinds.count("mamba"), kinds.count("attention")
    mlp = counts.mlp_matmul_params(model)
    d, h = model["d_model"], model["n_heads"]
    dh = model.get("head_dim") or d // h
    params = (mamba * (mamba_matmul_params(model) + mlp)
              + attention * (counts.attention_matmul_params(model) + mlp)
              + d * model["vocab_size"])
    forward = (2.0 * params * batch * seq
               + attention * counts.flash_flops(batch, h, seq, dh,
                                                causal=True)
               + mamba * ssd_flops(batch, seq, ssm_heads(model),
                                   model["ssm_head_dim"], model["ssm_state"],
                                   min(STEP_CHUNK, seq)))
    return 3.0 * forward
