"""How full the blocks are that the routed experts' products walk, in %: over
the window's `moe` journal events (train/loop.py, one an epoch) and the
expert layers that route every position, the choices that fell on held
experts over the rows of the dispatch's live blocks, Σ `held_slots` ÷ Σ
(`live_blocks` × `block_rows`).  A block is `block_rows` rows of one expert
(ops/routed_experts.plan_dispatch): an expert whose tokens end partway
through a block leaves the rest of it padding, which the products still
multiply.  The layer after the last sequence mixer routes one position a
row, a handful of tokens in blocks of a few rows, and is left out, as
`moe_load_imbalance` leaves it out.  None where no event carries the
counters."""


def read(run):
    held = walked = 0
    for record in run["journal"]:
        if record.get("kind") != "moe":
            continue
        layers = [layer for layer in record["layers"]
                  if "live_blocks" in layer and "block_rows" in layer]
        every = max((layer["routed_slots"] for layer in layers), default=0)
        for layer in layers:
            if layer["routed_slots"] == every:
                held += layer["held_slots"]
                walked += layer["live_blocks"] * layer["block_rows"]
    return 100.0 * held / walked if walked else None
