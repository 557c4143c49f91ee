"""The train step's share of its roofline: the least time the chip could
take for the steps in the trace - the larger of FLOPs over peak FLOP/s and
bytes over peak bytes/s, both from `counts/<model_type>.py` - over the
summed device time of the step program's executions in the trace.  Which
bound applied is in `run["roofline_bound"]` and on standard error."""

import sys


def read(run: dict):
    trace = run.get("trace") or {}
    if not trace.get("module_s") or not trace.get("module_runs"):
        return None     # no step program in the trace: nothing to read
    peaks = run["peaks"]
    t_flops = (run["flops_per_sample"] * run["batch"]
               / peaks["bf16_flops_per_s"])
    t_bytes = run["bytes_per_step"] / peaks["hbm_bytes_per_s"]
    bound = "flops" if t_flops >= t_bytes else "bytes"
    run["roofline_bound"] = bound
    steps = trace["module_runs"] * run["steps_per_epoch"]
    print(f"perfbench: step roofline bound by {bound}: "
          f"{t_flops * 1e6:.2f} us of FLOPs, {t_bytes * 1e6:.2f} us of bytes "
          f"a step; {trace['module_s'] / steps * 1e6:.2f} us a step on the "
          f"device over {steps:.0f} steps", file=sys.stderr)
    return 100.0 * max(t_flops, t_bytes) * steps / trace["module_s"]
