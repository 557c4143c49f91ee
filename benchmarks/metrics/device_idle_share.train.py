"""Share of the traced window in which no operation ran on the device: 1 -
the union of the device-operation intervals over the window, from the
profiler trace (`tracered.reduce`), averaged over the chips."""


def read(run: dict):
    trace = run.get("trace") or {}
    if not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
