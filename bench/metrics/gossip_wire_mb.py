"""Megabytes each node sends per gossip round, counted in the compiled round's
HLO: every collective-permute's operand bytes times its source-target pairs,
summed and divided by the nodes."""


def read(ctx):
    wire = ctx.facts.get("wire_bytes_per_node")
    return None if wire is None else wire / 1e6
