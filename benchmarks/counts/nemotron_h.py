"""Operations and bytes one train step of the hybrid Mamba-2 / routed-expert
/ attention sequence scorer needs, from its shapes (`model_type`
`nemotron_h`; a sample is one row of `num_categorical` positions).

The same rule as `counts/mlp.py`: the work the algorithm requires, whatever
implements it, three times the forward pass for forward + backward;
rematerialized work is not counted.  A position's forward pass:

- `M`: the two projections at 2mn (hidden -> d_inner + d_conv + heads,
  d_inner -> hidden), the convolution (2 k a channel), and the recurrence as
  written: a head's state of P x N is decayed (PN), added to (2 PN) and
  read (2 PN) once a position.  Chunking trades those for masked (Q, Q)
  products; that is an implementation's choice and is not counted.
- `*`: q, k, v and o projections at 2mn, and the causal products: a query
  reads (T + 1) / 2 keys on average, 2 d for the score and 2 d for the
  value, a query head.
- `E`: the router (2 hidden x router_experts), the shared expert (two
  products at width moe_shared_expert_intermediate_size), and the routed
  experts held here at the share of a position's choices expected to fall
  on them: num_experts_per_tok x held / router_experts experts a position.
  The sort, gather and scatter of the dispatch move bytes and count no
  operation.

Only the last position reaches the head and an `E` block mixes nothing along
the sequence, so the blocks that follow the last `M` or `*` are needed at
one position a row and are counted at one.  Norms, activations, the gate,
the softmax, the head (one position a row) and the loss are left out, as in
an MFU.  Bytes: the batch's rows in their
wire format; every parameter but the token table and both Adadelta slots
read and written once; of the table the rows a batch touches, a row a
position (as `counts/deepfm.py` counts a row a field: the vocabulary is not
in it).
"""

from __future__ import annotations


def _dims(cfg: dict) -> dict:
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    return {"h": cfg["hidden_size"], "heads": heads, "p": p, "n": n,
            "d_inner": heads * p, "d_conv": heads * p + 2 * g * n,
            "k": cfg["conv_kernel"], "hq": cfg["num_attention_heads"],
            "hkv": cfg["num_key_value_heads"], "d": cfg["head_dim"],
            "router": cfg["deployment"]["router_experts"],
            "held": cfg["n_routed_experts"],
            "top_k": cfg["num_experts_per_tok"],
            "f": cfg["moe_intermediate_size"],
            "fs": cfg["moe_shared_expert_intermediate_size"],
            "t": cfg["num_categorical"], "v": cfg["vocab_size"]}


def block_flops_per_position(cfg: dict) -> dict:
    """Forward operations a position, by the pattern's letter."""
    s = _dims(cfg)
    mamba = (2 * s["h"] * (s["d_inner"] + s["d_conv"] + s["heads"])
             + 2 * s["k"] * s["d_conv"]
             + 5 * s["heads"] * s["p"] * s["n"]
             + 2 * s["d_inner"] * s["h"])
    attention = (2 * s["h"] * (2 * s["hq"] + 2 * s["hkv"]) * s["d"]
                 + 4 * s["d"] * s["hq"] * (s["t"] + 1) / 2)
    shared = 2 * s["h"] * s["router"] + 4 * s["h"] * s["fs"]
    routed = s["top_k"] * s["held"] / s["router"] * 4 * s["h"] * s["f"]
    return {"M": mamba, "*": attention, "E": shared + routed,
            "E.routed": routed}


def block_positions(cfg: dict) -> list[int]:
    """Positions of a row each block is needed at: every one up to the last
    sequence mixer, the last position alone after it."""
    pattern = cfg["hybrid_override_pattern"]
    last_mixer = max(pattern.rfind("M"), pattern.rfind("*"))
    return [cfg["num_categorical"] if i <= last_mixer else 1
            for i in range(len(pattern))]


def flops_per_sample(cfg: dict) -> float:
    per = block_flops_per_position(cfg)
    return 3.0 * sum(per[kind] * n for kind, n in zip(
        cfg["hybrid_override_pattern"], block_positions(cfg)))


def block_params(cfg: dict) -> dict:
    """Parameters a block, by the pattern's letter, norms in."""
    s = _dims(cfg)
    return {
        "M": (s["h"] + s["h"] * (s["d_inner"] + s["d_conv"] + s["heads"])
              + (s["k"] + 1) * s["d_conv"] + 3 * s["heads"] + s["d_inner"]
              + s["d_inner"] * s["h"]),
        "*": s["h"] + s["h"] * (2 * s["hq"] + 2 * s["hkv"]) * s["d"],
        "E": (s["h"] + s["h"] * s["router"] + 2 * s["held"] * s["h"] * s["f"]
              + 2 * s["h"] * s["fs"]),
    }


def params(cfg: dict) -> tuple[int, int]:
    """(parameters outside the token table, the table's)."""
    s, per = _dims(cfg), block_params(cfg)
    dense = (sum(per[kind] for kind in cfg["hybrid_override_pattern"])
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
