"""Operations and bytes a train step of a ``nemotron_h`` layer stack
requires, from the configuration's shapes: the yardstick of
``train_mfu.nemotron-h``, ``ssd_roofline_share``,
``moe_routed_roofline_share`` and ``flash_roofline_share.nemotron-h``.

``flops.py`` counts the classic block and ``flops_evabyte.py`` EvaByte's;
this stack has one sub-layer a block, by the letters of ``layer_pattern``.
As there, multiply and add count as two, the backward pass as twice the
forward, and recomputation under ``remat`` not at all.

Per token, forward, with ``d`` the model width:

  * ``M`` (Mamba-2; ``H`` heads of ``P``, ``G`` groups, state ``N``, conv of
    ``k`` taps, chunks of ``l``; ``d_inner = H P``, ``conv_dim = d_inner +
    2 G N``): the projections ``2 d (d_inner + conv_dim + H)`` in and ``2
    d_inner d`` out; the conv ``2 k conv_dim``; and the chunked scan's
    least work: ``C_t . B_u`` for the ``(l + 1) / 2`` positions of its chunk
    up to ``t`` in each group (``2 N`` a pair), those scores applied to
    ``x`` in each head (``2 P`` a pair), the token's ``x (x) B`` added to
    its chunk's state and the entering state read through ``C`` (``2 P N``
    a head each). The recurrence over chunk states is ``2 H P N / l`` a
    token and is left out, as are the decays' exponentials;
  * ``*`` (attention; ``h`` query and ``kv`` key/value heads of ``hd``): q
    and o ``2 x 2 d h hd``, k and v ``2 x 2 d kv hd``, scores and values
    over the causal half, ``4 h hd (s + 1) / 2``;
  * ``E`` (experts): the router ``2 d n_experts``; the shared expert ``4 d
    shared``; the routed products at the **expected** number of (token,
    expert) pairs that land on the held experts under uniform routing,
    ``top_k held / n_experts`` a token, ``4 d d_ff`` a pair. The record
    holds no routing, so the real load of a run is not in this count;

and once per token the head, ``2 d vocab``.
"""

from __future__ import annotations

from typing import Dict, Mapping


def _held(model: Mapping) -> int:
    held = model.get("moe_experts_held")
    return model["n_experts"] if held is None else held


def pairs_per_token(model: Mapping) -> float:
    """(token, held expert) pairs a token, expected under uniform routing."""
    return model["moe_top_k"] * _held(model) / model["n_experts"]


def scan_forward_flops_per_token(model: Mapping) -> float:
    """The chunked scan alone, one ``M`` layer."""
    heads, p = model["ssm_heads"], model["ssm_head_dim"]
    groups, n = model["ssm_groups"], model["ssm_state"]
    in_chunk = (model["ssm_chunk"] + 1) / 2.0
    return (groups * 2.0 * n * in_chunk + heads * 2.0 * p * in_chunk
            + 2 * heads * 2.0 * p * n)


def routed_forward_flops_per_token(model: Mapping) -> float:
    """The routed experts' two products alone, one ``E`` layer."""
    return pairs_per_token(model) * 4.0 * model["d_model"] * model["d_ff"]


def layer_forward_flops_per_token(model: Mapping, seq: int
                                  ) -> Dict[str, Dict[str, float]]:
    """``{letter: {part: operations}}`` for one layer of each kind."""
    d = model["d_model"]
    heads, p = model["ssm_heads"], model["ssm_head_dim"]
    d_inner = heads * p
    conv_dim = d_inner + 2 * model["ssm_groups"] * model["ssm_state"]
    h, hd = model["n_heads"], model["attn_head_dim"]
    kv = model.get("n_kv_heads") or h
    return {
        "M": {"in_proj": 2.0 * d * (d_inner + conv_dim + heads),
              "conv": 2.0 * model["ssm_conv"] * conv_dim,
              "scan": scan_forward_flops_per_token(model),
              "out_proj": 2.0 * d_inner * d},
        "*": {"qo": 4.0 * d * h * hd, "kv": 4.0 * d * kv * hd,
              "scores": 4.0 * h * hd * (seq + 1) / 2.0},
        "E": {"router": 2.0 * d * model["n_experts"],
              "shared": 4.0 * d * model["moe_shared_d_ff"],
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
    causal scores and values of every ``*`` layer at ``attn_head_dim``,
    forward and twice that backward, no recomputation counted."""
    scores = layer_forward_flops_per_token(model, seq)["*"]["scores"]
    return 3.0 * batch * seq * model["layer_pattern"].count("*") * scores


def scan_flops_per_step(model: Mapping, batch: int, seq: int) -> float:
    """What the scans alone must compute in one train step, every ``M``
    layer, forward and backward."""
    return (3.0 * batch * seq * model["layer_pattern"].count("M")
            * scan_forward_flops_per_token(model))


def scan_bytes_per_step(model: Mapping, batch: int, seq: int,
                        itemsize: int = 2) -> float:
    """The least HBM traffic of the scans in one train step: forward a
    scan reads ``x``, ``B``, ``C`` and writes ``y``; backward it reads
    them again with ``y``'s gradient and writes three gradients. Five
    passes over a ``(batch, seq, d_inner)`` array and three over the two
    ``(batch, seq, G N)`` ones, in the compute dtype, a layer; ``dt`` (a
    sixty-fourth of ``x``) and the chunk states are left out."""
    d_inner = model["ssm_heads"] * model["ssm_head_dim"]
    bc = 2 * model["ssm_groups"] * model["ssm_state"]
    return (batch * seq * (5.0 * d_inner + 3.0 * bc) * itemsize
            * model["layer_pattern"].count("M"))


def routed_flops_per_step(model: Mapping, batch: int, seq: int) -> float:
    """The routed experts' products in one train step at the expected
    pairs, every ``E`` layer, forward and backward."""
    return (3.0 * batch * seq * model["layer_pattern"].count("E")
            * routed_forward_flops_per_token(model))


def routed_bytes_per_step(model: Mapping, batch: int, seq: int,
                          itemsize: int = 2) -> float:
    """The least HBM traffic of the routed products in one train step: the
    held experts' two matrices read forward and backward and their
    gradient written once (three passes, in the compute dtype), and for
    every expected pair its row read and written forward and backward
    (``d``: four passes) with the hidden row between the products written
    and read (``d_ff``: two passes) a layer."""
    d, ff = model["d_model"], model["d_ff"]
    pairs = batch * seq * pairs_per_token(model)
    return ((3.0 * _held(model) * 2 * d * ff + pairs * (4.0 * d + 2.0 * ff))
            * itemsize * model["layer_pattern"].count("E"))
