"""Share of the chip's published ICI peak that one gossip round achieves on the
bytes its semantics require each node to receive ((N - 1) trees for
dissemination), over the mean round time of the traced window."""


def read(ctx):
    need = ctx.facts.get("required_bytes_per_node")
    if need is None or not ctx.window["units"]:
        return None
    round_s = ctx.window["seconds"] / ctx.window["units"]
    return 100.0 * need / round_s / (ctx.peaks["ici_bits_per_s"] / 8)
