"""Device busy time per training step: the union of the device's operation
intervals over the traced window, mean over the chips, per step."""


def read(ctx):
    if ctx.trace is None or "flops_per_step" not in ctx.facts or not ctx.window["units"]:
        return None
    return 1e3 * ctx.trace.busy_s / ctx.window["units"]
