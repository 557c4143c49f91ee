"""Operations and bytes one train step of the gated-DeltaNet / gated-attention
/ routed-expert sequence scorer needs, from its shapes (`model_type`
`qwen3_next`; a sample is one row of `num_categorical` positions).

The same rule as `counts/nemotron_h.py`: the work the algorithm requires,
whatever implements it, three times the forward pass for forward + backward;
rematerialized work is not counted.  A published layer is two blocks, its
mixer and its expert layer (`pattern`).  A position's forward pass:

- `L` (the gated delta rule): the three projections at 2mn (hidden -> q, k,
  v, z and b, a; the value heads' dims -> hidden), the convolution (2 k a
  channel), and the recurrence as written: a value head's state of d_k x d_v
  is decayed (d_k d_v), read at the key (2 d_k d_v), added to (2 d_k d_v)
  and read at the query (2 d_k d_v) once a position, 7 d_k d_v.  Chunking
  trades those for a triangular system and (C, C) products; that is an
  implementation's choice and is not counted.
- `A`: q (with its gate), k, v and o projections at 2mn, and the causal
  products: a query reads (T + 1) / 2 keys on average, 2 d for the score and
  2 d for the value, a query head.
- `F`: the router (2 hidden x router_experts), the shared expert (three
  products at width shared_expert_intermediate_size, and its gate's 2
  hidden), and the routed experts held here at the share of a position's
  choices expected to fall on them: num_experts_per_tok x held /
  router_experts experts a position, three products each.  The sort, gather
  and scatter of the dispatch move bytes and count no operation.

Only the last position reaches the head and an expert block mixes nothing
along the sequence, so the blocks that follow the last `L` or `A` are needed
at one position a row and are counted at one.  Norms, activations, gates,
the rotary term, the softmax, the head (one position a row) and the loss are
left out, as in an MFU.  Bytes: as `counts/nemotron_h.py` has them.
"""

from __future__ import annotations


def pattern(cfg: dict) -> str:
    """The stack's blocks, a letter each: layer l's mixer (`A` full
    attention where `(l + 1) % full_attention_interval == 0`, `L` the gated
    delta rule otherwise), then its expert layer `F`.  The one spelling:
    `jobs/qwen3_next.py` and `reference/qwen3_next.py` import it."""
    every = cfg["full_attention_interval"]
    return "".join(("A" if (layer + 1) % every == 0 else "L") + "F"
                   for layer in range(cfg["num_hidden_layers"]))


def _dims(cfg: dict) -> dict:
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return {"h": cfg["hidden_size"], "hv": hv, "dk": dk, "dv": dv,
            "d_value": hv * dv, "d_conv": 2 * hk * dk + hv * dv,
            "k": cfg["linear_conv_kernel_dim"],
            "hq": cfg["num_attention_heads"],
            "hkv": cfg["num_key_value_heads"], "d": cfg["head_dim"],
            "router": cfg["deployment"]["router_experts"],
            "held": cfg["num_experts"], "top_k": cfg["num_experts_per_tok"],
            "f": cfg["moe_intermediate_size"],
            "fs": cfg["shared_expert_intermediate_size"],
            "t": cfg["num_categorical"], "v": cfg["vocab_size"]}


def block_flops_per_position(cfg: dict) -> dict:
    """Forward operations a position, by the pattern's letter."""
    s = _dims(cfg)
    linear = (2 * s["h"] * (s["d_conv"] + s["d_value"] + 2 * s["hv"])
              + 2 * s["k"] * s["d_conv"]
              + 7 * s["hv"] * s["dk"] * s["dv"]
              + 2 * s["d_value"] * s["h"])
    attention = (2 * s["h"] * (3 * s["hq"] + 2 * s["hkv"]) * s["d"]
                 + 4 * s["d"] * s["hq"] * (s["t"] + 1) / 2)
    shared = (2 * s["h"] * s["router"] + 6 * s["h"] * s["fs"] + 2 * s["h"])
    routed = s["top_k"] * s["held"] / s["router"] * 6 * s["h"] * s["f"]
    return {"L": linear, "A": attention, "F": shared + routed,
            "F.routed": routed}


def block_positions(cfg: dict) -> list[int]:
    """Positions of a row each block is needed at: every one up to the last
    sequence mixer, the last position alone after it."""
    p = pattern(cfg)
    last_mixer = max(p.rfind("L"), p.rfind("A"))
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
        "L": (s["h"] + s["h"] * (s["d_conv"] + s["d_value"] + 2 * s["hv"])
              + s["k"] * s["d_conv"] + 2 * s["hv"] + s["dv"]
              + s["d_value"] * s["h"]),
        "A": (s["h"] + s["h"] * (3 * s["hq"] + 2 * s["hkv"]) * s["d"]
              + 2 * s["d"]),
        "F": (s["h"] + s["h"] * s["router"] + 3 * s["held"] * s["h"] * s["f"]
              + 3 * s["h"] * s["fs"] + s["h"]),
    }


def params(cfg: dict) -> tuple[int, int]:
    """(parameters outside the token table, the table's)."""
    s, per = _dims(cfg), block_params(cfg)
    dense = (sum(per[kind] for kind in pattern(cfg))
             + s["h"] + s["h"] + 1)                    # final norm, the head
    return dense, s["v"] * s["h"]


def row_wire_bytes(cfg: dict) -> int:
    """One row in the resident tier: a float32 id a position, a u8 label,
    an f32 weight where the rows carry one."""
    return cfg["num_categorical"] * 4 + 1 + (4 if cfg.get("with_weight")
                                             else 0)


def bytes_per_step(cfg: dict, batch: int) -> float:
    s = _dims(cfg)
    dense, _ = params(cfg)
    touched = batch * s["t"] * s["h"]
    return batch * row_wire_bytes(cfg) + 6 * 4 * (dense + touched)
