"""Operations and bytes one train step of the gated-short-convolution /
QK-normed attention / routed-expert sequence scorer needs, from its shapes
(`model_type` `lfm2_moe`; a sample is one row of `num_categorical`
positions).

The same rule as `counts/nemotron_h.py`: the work the algorithm requires,
whatever implements it, three times the forward pass for forward + backward;
rematerialized work is not counted.  A published layer is two blocks, its
mixer and its feed-forward (`pattern`).  A position's forward pass:

- `V` (the gated short convolution): the two projections at 2mn (hidden ->
  the three gates' 3 x hidden, hidden -> hidden) and the depthwise
  convolution (2 taps a channel).  The two gating products are elementwise
  and are left out, as activations are.
- `Q` (attention): q, k, v and o projections at 2mn, and the causal
  products: a query reads (T + 1) / 2 keys on average, 2 d for the score
  and 2 d for the value, a query head.
- `D`: three products at `intermediate_size`.
- `G`: the router (2 hidden x router_experts) and the routed experts held
  here at the share of a position's choices expected to fall on them:
  num_experts_per_tok x held / router_experts experts a position, three
  products each.  There is no shared expert.  The sort, gather and scatter
  of the dispatch move bytes and count no operation.

Only the last position reaches the head and neither `D` nor `G` mixes
anything along the sequence, so the blocks that follow the last mixer are
needed at one position a row and are counted at one.  Norms, activations,
the rotary term, the softmax, the head (one position a row) and the loss are
left out, as in an MFU.  Bytes: as `counts/nemotron_h.py` has them.
"""

from __future__ import annotations

from .nemotron_h import row_wire_bytes

#: a published `layer_types` entry -> the program's mixer letter
MIXERS = {"conv": "V", "full_attention": "Q"}


def pattern(cfg: dict) -> str:
    """The stack's blocks, a letter each: layer l's mixer (`V` a `conv`
    layer, `Q` a `full_attention` one, by `layer_types`), then its
    feed-forward - `D` the dense MLP in the `num_dense_layers` leading
    layers, `G` the expert layer in every layer after them.  The one
    spelling: `jobs/lfm2_moe.py` and `reference/lfm2_moe.py` import it."""
    types = cfg["layer_types"]
    if len(types) != cfg["num_hidden_layers"]:
        raise ValueError(f"layer_types has {len(types)} entries for "
                         f"num_hidden_layers {cfg['num_hidden_layers']}")
    dense = cfg["num_dense_layers"]
    return "".join(MIXERS[t] + ("D" if layer < dense else "G")
                   for layer, t in enumerate(types))


def head_dim(cfg: dict) -> int:
    """A head's width: the file states none, so hidden / heads, as the
    family's code has it."""
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _dims(cfg: dict) -> dict:
    return {"h": cfg["hidden_size"], "hq": cfg["num_attention_heads"],
            "hkv": cfg["num_key_value_heads"], "d": head_dim(cfg),
            "taps": cfg["conv_L_cache"], "fd": cfg["intermediate_size"],
            "router": cfg["deployment"]["router_experts"],
            "held": cfg["num_experts"],
            "top_k": cfg["num_experts_per_tok"],
            "f": cfg["moe_intermediate_size"],
            "t": cfg["num_categorical"], "v": cfg["vocab_size"]}


def block_flops_per_position(cfg: dict) -> dict:
    """Forward operations a position, by the pattern's letter."""
    s = _dims(cfg)
    products = 4 * s["d"] * s["hq"] * (s["t"] + 1) / 2
    routed = s["top_k"] * s["held"] / s["router"] * 6 * s["h"] * s["f"]
    return {"V": 2 * 4 * s["h"] * s["h"] + 2 * s["taps"] * s["h"],
            "Q": 2 * s["h"] * (2 * s["hq"] + 2 * s["hkv"]) * s["d"]
            + products,
            "Q.products": products, "D": 6 * s["h"] * s["fd"],
            "G": 2 * s["h"] * s["router"] + routed, "G.routed": routed}


def block_positions(cfg: dict) -> list[int]:
    """Positions of a row each block is needed at: every one up to the last
    sequence mixer, the last position alone after it."""
    p = pattern(cfg)
    last_mixer = max(p.rfind(m) for m in MIXERS.values())
    return [cfg["num_categorical"] if i <= last_mixer else 1
            for i in range(len(p))]


def flops_per_sample(cfg: dict) -> float:
    per = block_flops_per_position(cfg)
    return 3.0 * sum(per[kind] * n for kind, n in zip(
        pattern(cfg), block_positions(cfg)))


def block_params(cfg: dict) -> dict:
    """Parameters a block, by the pattern's letter, norms in."""
    s = _dims(cfg)
    return {
        "V": s["h"] + 3 * s["h"] * s["h"] + s["taps"] * s["h"]
        + s["h"] * s["h"],
        "Q": (s["h"] + s["h"] * (2 * s["hq"] + 2 * s["hkv"]) * s["d"]
              + 2 * s["d"]),
        "D": s["h"] + 3 * s["h"] * s["fd"],
        "G": s["h"] + s["h"] * s["router"] + 3 * s["held"] * s["h"] * s["f"],
    }


def params(cfg: dict) -> tuple[int, int]:
    """(parameters outside the token table, the table's)."""
    s, per = _dims(cfg), block_params(cfg)
    dense = (sum(per[kind] for kind in pattern(cfg))
             + s["h"] + s["h"] + 1)                    # final norm, the head
    return dense, s["v"] * s["h"]


def bytes_per_step(cfg: dict, batch: int) -> float:
    s = _dims(cfg)
    dense, _ = params(cfg)
    touched = batch * s["t"] * s["h"]
    return batch * row_wire_bytes(cfg) + 6 * 4 * (dense + touched)
