"""Operations and bytes one train step of the latent-attention / dense-MLP /
routed-expert sequence scorer needs, from its shapes (`model_type`
`joyai_llm_flash`; a sample is one row of `num_categorical` positions).

The same rule as `counts/nemotron_h.py`: the work the algorithm requires,
whatever implements it, three times the forward pass for forward + backward;
rematerialized work is not counted.  A published layer is two blocks, its
mixer and its feed-forward (`pattern`).  A position's forward pass:

- `C` (latent attention): the five projections at 2mn as published (hidden
  -> q's latent -> the heads' queries; hidden -> the keys' and values' latent
  and the one rotary key; that latent -> the heads' keys and values, computed
  once a position, whatever is recomputed under remat; the heads' values ->
  hidden), and the causal products: a query reads (T + 1) / 2 keys on
  average, 2 (qk_nope_head_dim + qk_rope_head_dim) for the score and
  2 v_head_dim for the value, a head.  The rotary key is one head's, but a
  head's score still takes its product with it: it is counted a head.
- `D`: three products at `intermediate_size`.
- `G`: the router (2 hidden x router_experts), the shared expert (three
  products at n_shared_experts x moe_intermediate_size, no gate), and the
  routed experts held here at the share of a position's choices expected to
  fall on them: num_experts_per_tok x held / router_experts experts a
  position, three products each.  The sort, gather and scatter of the
  dispatch move bytes and count no operation.

Only the last position reaches the head and neither `D` nor `G` mixes
anything along the sequence, so the blocks that follow the last `C` are
needed at one position a row and are counted at one.  Norms, activations,
the rotary term, the softmax, the head (one position a row) and the loss are
left out, as in an MFU.  Bytes: as `counts/nemotron_h.py` has them.
"""

from __future__ import annotations

from .nemotron_h import row_wire_bytes


def pattern(cfg: dict) -> str:
    """The stack's blocks, a letter each: layer l's mixer `C`, then its
    feed-forward - `D` the dense MLP in the `first_k_dense_replace` leading
    layers, `G` the expert layer in every layer after them
    (`moe_layer_freq` 1).  The one spelling: `jobs/joyai_llm_flash.py` and
    `reference/joyai_llm_flash.py` import it."""
    dense = cfg["first_k_dense_replace"]
    return "".join("C" + ("D" if layer < dense else "G")
                   for layer in range(cfg["num_hidden_layers"]))


def _dims(cfg: dict) -> dict:
    return {"h": cfg["hidden_size"], "hq": cfg["num_attention_heads"],
            "ql": cfg["q_lora_rank"], "kvl": cfg["kv_lora_rank"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "fd": cfg["intermediate_size"],
            "router": cfg["deployment"]["router_experts"],
            "held": cfg["n_routed_experts"],
            "top_k": cfg["num_experts_per_tok"],
            "f": cfg["moe_intermediate_size"],
            "fs": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
            "t": cfg["num_categorical"], "v": cfg["vocab_size"]}


def _latent_projections(s: dict) -> int:
    """Entries of a `C` block's five projection matrices."""
    return (s["h"] * s["ql"] + s["ql"] * s["hq"] * (s["dn"] + s["dr"])
            + s["h"] * (s["kvl"] + s["dr"])
            + s["kvl"] * s["hq"] * (s["dn"] + s["dv"])
            + s["hq"] * s["dv"] * s["h"])


def block_flops_per_position(cfg: dict) -> dict:
    """Forward operations a position, by the pattern's letter."""
    s = _dims(cfg)
    products = (2 * (s["dn"] + s["dr"]) + 2 * s["dv"]) * s["hq"] \
        * (s["t"] + 1) / 2
    shared = 2 * s["h"] * s["router"] + 6 * s["h"] * s["fs"]
    routed = s["top_k"] * s["held"] / s["router"] * 6 * s["h"] * s["f"]
    return {"C": 2 * _latent_projections(s) + products,
            "C.products": products, "D": 6 * s["h"] * s["fd"],
            "G": shared + routed, "G.routed": routed}


def block_positions(cfg: dict) -> list[int]:
    """Positions of a row each block is needed at: every one up to the last
    sequence mixer, the last position alone after it."""
    p = pattern(cfg)
    return [cfg["num_categorical"] if i <= p.rfind("C") else 1
            for i in range(len(p))]


def flops_per_sample(cfg: dict) -> float:
    per = block_flops_per_position(cfg)
    return 3.0 * sum(per[kind] * n for kind, n in zip(
        pattern(cfg), block_positions(cfg)))


def block_params(cfg: dict) -> dict:
    """Parameters a block, by the pattern's letter, norms in."""
    s = _dims(cfg)
    return {
        "C": s["h"] + _latent_projections(s) + s["ql"] + s["kvl"],
        "D": s["h"] + 3 * s["h"] * s["fd"],
        "G": (s["h"] + s["h"] * s["router"] + 3 * s["held"] * s["h"] * s["f"]
              + 3 * s["h"] * s["fs"]),
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
