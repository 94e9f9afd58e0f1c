"""Operations a train step requires, from the configuration's shapes.

The yardstick for ``train_mfu`` and ``flash_roofline_share``: what the
forward and backward passes need, never what the program happens to
execute (recomputation under ``remat`` does not count).

``train_flops_per_step`` is ``bench.py``'s function of the same name with
one correction: ``bench.py`` charges the four attention projections at
``8 b s d^2``, which counts k and v at full width; under grouped-query
attention they project to ``kv_heads * head_dim`` columns. With
``n_kv_heads == n_heads`` the two functions agree exactly
(``benchmark/tests/test_flops.py``).
"""

from __future__ import annotations

from typing import Mapping


def _dims(model: Mapping):
    d, h = model["d_model"], model["n_heads"]
    kv = model.get("n_kv_heads") or h
    return d, h, kv, d // h


def forward_flops_per_token(model: Mapping, seq: int) -> float:
    """Matmul operations of one forward pass for one token of a causal
    sequence of ``seq`` tokens (multiply and add count as two)."""
    d, h, kv, hd = _dims(model)
    ff, layers, vocab = model["d_ff"], model["n_layers"], model["vocab"]
    qkvo = 2 * d * (h * hd) * 2 + 2 * d * (kv * hd) * 2  # q, o; k, v
    ffn = 4 * d * ff                                     # two matrices
    attn = 2 * seq * h * hd          # scores + values, 4 s h hd, halved: causal
    return float(layers * (qkvo + ffn + attn) + 2 * d * vocab)


def train_flops_per_step(model: Mapping, batch: int, seq: int) -> float:
    """Forward plus backward (twice the forward) for ``batch`` sequences."""
    return 3.0 * batch * seq * forward_flops_per_token(model, seq)


def flash_flops_per_step(model: Mapping, batch: int, seq: int) -> float:
    """What the attention kernels alone must do in one train step: causal
    scores and values forward (``2 b h s^2 hd``), twice that backward, no
    recomputation counted: ``6 b h s^2 hd`` a layer."""
    _, h, _, hd = _dims(model)
    return 6.0 * batch * h * seq * seq * hd * model["n_layers"]
