"""Operations and bytes an EvaByte train step requires, from the
configuration's shapes: the yardstick of ``train_mfu.evabyte`` and
``eva_roofline_share``.

``flops.py`` counts a two-matrix FFN, full causal attention and a tied
vocabulary; this model has a three-matrix gated FFN, EVA attention (the
query's own window causally, plus one summary per chunk of every earlier
window) and an untied head of ``n_pred_heads x vocab`` columns. As there,
multiply and add count as two, the backward pass as twice the forward, and
recomputation under ``remat`` not at all.

Per token and layer, forward, with ``d`` the model width, ``h`` heads of
``hd``, windows of ``W``, chunks of ``C`` and a sequence of ``s`` (whole
windows, or one shorter window):

  * q, k, v, o projections: ``8 d^2`` (``h hd = d``, no grouped heads);
  * FFN: ``6 d ff`` (gate, up, down);
  * EVA scores and values: ``4 h hd`` a (query, key) pair. A query at
    offset ``r`` of its window sees ``r + 1`` keys, ``(W + 1) / 2`` on
    average; a query of window ``w`` sees ``w W / C`` summaries,
    ``(s / W - 1) W / (2 C)`` on average;
  * pooling: a score ``k . phi`` (``2 hd``) and the weighted sums for the
    summary key and value (``2 x 2 hd``) per token and head: ``6 h hd``;

and once per token the head, ``2 d vocab n_pred_heads``.
"""

from __future__ import annotations

from typing import Dict, Mapping


def _geometry(model: Mapping, seq: int):
    window = min(model["eva_window"], seq)
    if seq % window or window % model["eva_chunk"]:
        raise ValueError(f"seq {seq} is not whole windows of {window} made "
                         f"of whole chunks of {model['eva_chunk']}")
    local = (window + 1) / 2.0
    remote = (seq // window - 1) * (window // model["eva_chunk"]) / 2.0
    return local, remote


def eva_forward_flops_per_token(model: Mapping, seq: int) -> Dict[str, float]:
    """The EVA op alone, one layer: ``{"attend", "pool"}``."""
    local, remote = _geometry(model, seq)
    d = model["d_model"]                   # = heads x head size
    return {"attend": 4.0 * d * (local + remote), "pool": 6.0 * d}


def forward_parts_per_token(model: Mapping, seq: int) -> Dict[str, float]:
    """Matmul operations of one forward pass for one token, by part (the
    per-layer parts already times ``n_layers``)."""
    d, ff, layers = model["d_model"], model["d_ff"], model["n_layers"]
    eva = eva_forward_flops_per_token(model, seq)
    return {
        "qkvo": layers * 8.0 * d * d,
        "ffn": layers * 6.0 * d * ff,
        "eva_attend": layers * eva["attend"],
        "eva_pool": layers * eva["pool"],
        "head": 2.0 * d * model["vocab"] * model["n_pred_heads"],
    }


def forward_flops_per_token(model: Mapping, seq: int) -> float:
    return float(sum(forward_parts_per_token(model, seq).values()))


def train_flops_per_step(model: Mapping, batch: int, seq: int) -> float:
    """Forward plus backward (twice the forward) for ``batch`` sequences."""
    return 3.0 * batch * seq * forward_flops_per_token(model, seq)


def eva_flops_per_step(model: Mapping, batch: int, seq: int) -> float:
    """What the EVA op alone must compute in one train step, every layer:
    pooling, scores and values forward, twice that backward."""
    eva = eva_forward_flops_per_token(model, seq)
    return (3.0 * batch * seq * model["n_layers"]
            * (eva["attend"] + eva["pool"]))


def eva_bytes_per_step(model: Mapping, batch: int, seq: int,
                       itemsize: int = 2) -> float:
    """The least HBM traffic of the EVA op in one train step: forward it
    reads q, k, v and writes its output; backward it reads q, k, v, the
    output and the output's gradient and writes three gradients. Twelve
    passes over a ``(batch, seq, d_model)`` array of the compute dtype a
    layer; the summaries, the statistics and phi, mu are a hundredth of
    that and are left out."""
    return (12.0 * batch * seq * model["d_model"] * itemsize
            * model["n_layers"])
