"""The share of the routed layers' input for which this chip's expert layer
did no work, in %: over the window's `moe` journal events (train/loop.py,
one an epoch) and the expert layers that route every position, the tokens
none of whose choices fell on a held expert over all the layer's tokens,
Σ `tokens_unrouted` ÷ Σ `tokens`.  Only an expert layer with no shared
expert counts them (models/block_stack._dispatch_counters): there such a
token leaves the layer unchanged, the part of the input a dispatch could
skip.  The layer after the last sequence mixer routes one position a row
and is left out, as `moe_load_imbalance` leaves it out.  None where no
event carries the counters."""


def read(run):
    tokens = unrouted = 0
    for record in run["journal"]:
        if record.get("kind") != "moe":
            continue
        layers = [layer for layer in record["layers"]
                  if "tokens_unrouted" in layer and "tokens" in layer]
        every = max((layer["routed_slots"] for layer in layers), default=0)
        for layer in layers:
            if layer["routed_slots"] == every:
                tokens += layer["tokens"]
                unrouted += layer["tokens_unrouted"]
    return 100.0 * unrouted / tokens if tokens else None
