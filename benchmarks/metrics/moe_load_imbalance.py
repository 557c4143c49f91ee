"""The routed expert layers' load imbalance: the busiest held expert's
tokens over the mean held expert's, in the worst `E` layer, over the
window's epochs (1.0 is an even load; lower is better).  Read from the
`moe` journal events `train()` writes once an epoch (`train/loop.py`), whose
counts the step sums on the device.  The worst layer is looked for among
those that route every position (the most `routed_slots`): an `E` block
after the last sequence mixer routes one position a row, a handful of
tokens whose ratio is chance and moves no time.  A program that journals no
such event gives nothing to read."""


def read(run: dict):
    events = [r for r in run["journal"] if r.get("kind") == "moe"]
    if not events:
        return None
    layers = events[0]["layers"]
    most = max(layer.get("routed_slots", 0) for layer in layers)
    worst = None
    for i, layer in enumerate(layers):
        if layer.get("routed_slots", 0) < most:
            continue
        held = len(layer["tokens_per_expert"])
        load = [sum(e["layers"][i]["tokens_per_expert"][j] for e in events)
                for j in range(held)]
        if sum(load) > 0:
            ratio = max(load) * held / sum(load)
            worst = ratio if worst is None else max(worst, ratio)
    return worst
