"""Operations and bytes a train step of an ``lfm2_moe`` layer stack
requires, from the configuration's shapes: the yardstick of
``train_mfu.lfm2``, ``shortconv_roofline_share``,
``moe_routed_roofline_share.lfm2`` and ``flash_roofline_share.lfm2``.

``flops_nemotron_h.py`` counts the ``M`` / ``*`` / ``E`` stack with relu²
experts; this one the letters ``C``, ``*``, ``F`` and ``E`` of
``layer_pattern`` with SwiGLU (three-matrix) experts and dense FFN. As
there, multiply and add count as two, an elementwise product as one, the
backward pass as twice the forward, and recomputation under ``remat`` not
at all.

Per token, forward, with ``d`` the model width:

  * ``C`` (gated short conv of ``k`` = 3 taps, the program's ``TAPS``):
    the projections ``2 d 3d`` in and ``2 d d`` out, the conv ``2 k d``
    and its two gates ``2 d``;
  * ``*`` (attention; ``h`` query and ``kv`` key/value heads of ``hd``): q
    and o ``2 x 2 d h hd``, k and v ``2 x 2 d kv hd``, scores and values
    over the causal half, ``4 h hd (s + 1) / 2``; the q/k norms and rope
    are left out;
  * ``F`` (dense SwiGLU of ``dense_d_ff``): ``6 d dense_d_ff``;
  * ``E`` (experts): the router ``2 d n_experts``; the routed products at
    the **expected** number of (token, expert) pairs that land on the held
    experts under uniform routing, ``top_k held / n_experts`` a token,
    ``6 d d_ff`` a pair (three matrices). The record holds no routing, so
    the real load of a run is not in this count;

and once per token the head, ``2 d vocab``.
"""

from __future__ import annotations

from typing import Dict, Mapping

_TAPS = 3       # the short conv's taps: conv_L_cache of lfm2_moe


def _held(model: Mapping) -> int:
    held = model.get("moe_experts_held")
    return model["n_experts"] if held is None else held


def _head_dim(model: Mapping) -> int:
    return model.get("attn_head_dim") or model["d_model"] // model["n_heads"]


def pairs_per_token(model: Mapping) -> float:
    """(token, held expert) pairs a token, expected under uniform routing."""
    return model["moe_top_k"] * _held(model) / model["n_experts"]


def routed_forward_flops_per_token(model: Mapping) -> float:
    """The routed experts' three products alone, one ``E`` layer."""
    return pairs_per_token(model) * 6.0 * model["d_model"] * model["d_ff"]


def layer_forward_flops_per_token(model: Mapping, seq: int
                                  ) -> Dict[str, Dict[str, float]]:
    """``{letter: {part: operations}}`` for one layer of each kind."""
    d = model["d_model"]
    h, hd = model["n_heads"], _head_dim(model)
    kv = model.get("n_kv_heads") or h
    return {
        "C": {"in_proj": 2.0 * d * 3 * d, "out_proj": 2.0 * d * d,
              "conv": 2.0 * _TAPS * d, "gates": 2.0 * d},
        "*": {"qo": 4.0 * d * h * hd, "kv": 4.0 * d * kv * hd,
              "scores": 4.0 * h * hd * (seq + 1) / 2.0},
        "F": {"dense": 6.0 * d * model["dense_d_ff"]},
        "E": {"router": 2.0 * d * model["n_experts"],
              "routed": routed_forward_flops_per_token(model)},
    }


def forward_parts_per_token(model: Mapping, seq: int) -> Dict[str, float]:
    """Operations of one forward pass for one token: each kind of layer
    times its count in ``layer_pattern``, and the head."""
    pattern = model["layer_pattern"]
    layers = layer_forward_flops_per_token(model, seq)
    out = {kind: pattern.count(kind) * sum(layers[kind].values())
           for kind in layers}
    out["head"] = 2.0 * model["d_model"] * model["vocab"]
    return out


def forward_flops_per_token(model: Mapping, seq: int) -> float:
    return float(sum(forward_parts_per_token(model, seq).values()))


def train_flops_per_step(model: Mapping, batch: int, seq: int) -> float:
    """Forward plus backward (twice the forward) for ``batch`` sequences."""
    return 3.0 * batch * seq * forward_flops_per_token(model, seq)


def flash_flops_per_step(model: Mapping, batch: int, seq: int) -> float:
    """What the attention kernels alone must do in one train step: the
    causal scores and values of every ``*`` layer at its head size,
    forward and twice that backward, no recomputation counted."""
    scores = layer_forward_flops_per_token(model, seq)["*"]["scores"]
    return 3.0 * batch * seq * model["layer_pattern"].count("*") * scores


def shortconv_flops_per_step(model: Mapping, batch: int, seq: int) -> float:
    """Every ``C`` operator in one train step, forward and backward."""
    return (3.0 * batch * seq * model["layer_pattern"].count("C")
            * sum(layer_forward_flops_per_token(model, seq)["C"].values()))


def shortconv_bytes_per_step(model: Mapping, batch: int, seq: int,
                             itemsize: int = 2) -> float:
    """The least HBM traffic of the ``C`` operators in one train step: the
    two projections' ``4 d^2`` weights read forward and backward and their
    gradient written once (three passes), and per token the operator's
    input and output rows: read and written forward, read again with the
    output's gradient and the input's gradient written backward (``d``:
    four passes), in the compute dtype; what lies between the projections
    is left out (it need never leave the chip)."""
    d = model["d_model"]
    return ((3.0 * 4 * d * d + batch * seq * 4.0 * d) * itemsize
            * model["layer_pattern"].count("C"))


def routed_flops_per_step(model: Mapping, batch: int, seq: int) -> float:
    """The routed experts' products in one train step at the expected
    pairs, every ``E`` layer, forward and backward."""
    return (3.0 * batch * seq * model["layer_pattern"].count("E")
            * routed_forward_flops_per_token(model))


def routed_bytes_per_step(model: Mapping, batch: int, seq: int,
                          itemsize: int = 2) -> float:
    """The least HBM traffic of the routed products in one train step: the
    held experts' three matrices read forward and backward and their
    gradient written once (three passes, in the compute dtype), and for
    every expected pair its row read and written forward and backward
    (``d``: four passes) with the two hidden rows between the products
    written and read (``d_ff``: four passes) a layer."""
    d, ff = model["d_model"], model["d_ff"]
    pairs = batch * seq * pairs_per_token(model)
    return ((3.0 * _held(model) * 3 * d * ff + pairs * (4.0 * d + 4.0 * ff))
            * itemsize * model["layer_pattern"].count("E"))
