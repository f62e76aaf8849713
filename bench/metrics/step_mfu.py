"""Model FLOPs utilization of the training step: the model FLOPs of the steps
in the traced window (bench/counts.py) over the window and the chips' peak
bf16 FLOP/s."""


def read(ctx):
    flops = ctx.facts.get("flops_per_step")
    if not flops or not ctx.window["units"]:
        return None
    return 100.0 * flops * ctx.window["units"] / ctx.window["seconds"] \
        / (ctx.cell.chips * ctx.peaks["bf16_flops_per_s"])
