"""Operation and byte counts of one BERT-MLM training step, from shapes.

One function per part of the forward pass, each returning the matmul
FLOPs (2 per multiply-add) for a batch of ``B`` sequences of ``S``
tokens.  ``c`` is a configuration file's dict (``bench/configs``).
Backward costs twice the forward; recomputation under ``remat`` is work
the device does but not work the model needs, so it counts toward a
kernel's roofline (the time includes it) and never toward ``mfu``.

Attention and cross-entropy have functions of their own, so that a
roofline of whatever kernel computes them is taken against the same
count.
"""
from __future__ import annotations

from typing import Dict, Tuple

F32 = 4  # bytes: the configurations train in float32


def _dims(c):
    d, h = c["hidden_size"], c["num_attention_heads"]
    return (c["num_hidden_layers"], d, h, d // h, c["intermediate_size"],
            c["vocab_size"])


def qkvo_projections(c, B, S) -> float:
    L, d, H, D, _, _ = _dims(c)
    return L * 2.0 * B * S * d * (4 * H * D)


def ffn(c, B, S) -> float:
    L, d, _, _, f, _ = _dims(c)
    return L * 2.0 * B * S * 2 * d * f


def attention(c, B, S) -> float:
    """Scores ``q k^T`` and values ``p v`` of every layer."""
    L, _, H, D, _, _ = _dims(c)
    return L * 2.0 * 2 * B * H * S * S * D


def mlm_transform(c, B, S) -> float:
    _, d, _, _, _, _ = _dims(c)
    return 2.0 * B * S * d * d


def decoder(c, B, S) -> float:
    """The tied decoder, over every position, as the program computes it."""
    _, d, _, _, _, V = _dims(c)
    return 2.0 * B * S * d * V


def embeddings(c, B, S) -> float:
    """Token and position lookups are gathers: no matmul FLOPs."""
    return 0.0


PARTS = {"qkvo_projections": qkvo_projections, "ffn": ffn,
         "attention": attention, "mlm_transform": mlm_transform,
         "decoder": decoder, "embeddings": embeddings}


def cross_entropy(c, B, S) -> Tuple[float, float]:
    """(FLOPs, bytes) of the log-softmax and the label pick over the
    logits: max, subtract, exp, sum and log per logit, one f32 read."""
    _, _, _, _, _, V = _dims(c)
    n = float(B) * S * V
    return 5.0 * n, F32 * n


def forward(c, B, S) -> Dict[str, float]:
    return {k: fn(c, B, S) for k, fn in PARTS.items()}


def model_flops_per_step(c, B, S) -> float:
    """Forward plus backward (3x forward) of the matmuls the model needs."""
    return 3.0 * sum(forward(c, B, S).values())


def _matmul_bytes_fwd(c, B, S) -> Tuple[float, float]:
    """Operands and results of every forward matmul, read and written
    once: (all of them, the ffn down-projections alone)."""
    L, d, H, D, f, V = _dims(c)
    t = float(B) * S
    hd = H * D
    down = t * f + f * d + t * d
    per_layer = (
        (t * d + d * 3 * hd + t * 3 * hd)          # q, k, v projections
        + (t * hd + hd * d + t * d)                # output projection
        + (t * d + d * f + t * f)                  # ffn up
        + down                                     # ffn down
        + (2 * t * hd + B * H * S * S)             # scores
        + (B * H * S * S + 2 * t * hd))            # values
    head = (t * d + d * d + t * d) + (t * d + V * d + t * V)
    return F32 * (L * per_layer + head), F32 * L * down


def step_matmuls(c, B, S, *, recompute: bool = True) -> Tuple[float, float]:
    """(FLOPs, bytes) of the matmuls one step executes.  Each backward
    matmul (input and weight gradient) moves as much as its forward one.
    With ``recompute`` the layers and the loss head run forward again in
    backward, all but each layer's ffn down-projection, whose output no
    gradient needs."""
    fwd = sum(forward(c, B, S).values())
    fwd_bytes, down_bytes = _matmul_bytes_fwd(c, B, S)
    if not recompute:
        return 3.0 * fwd, 3.0 * fwd_bytes
    down = ffn(c, B, S) / 2
    return 4.0 * fwd - down, 4.0 * fwd_bytes - down_bytes
