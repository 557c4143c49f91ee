"""The whole step's share of the chip's peak: analytic forward + backward
FLOPs a sample (`counts/<model_type>.py`) x rows trained in the window over
(the window's wall x chips x peak bf16 FLOP/s from `peaks.json`)."""


def read(run: dict):
    if run["wall_s"] <= 0 or not run["rows"]:
        return None
    peak = run["peaks"]["bf16_flops_per_s"]
    return (100.0 * run["flops_per_sample"] * run["rows"]
            / (run["wall_s"] * run["chips"] * peak))
