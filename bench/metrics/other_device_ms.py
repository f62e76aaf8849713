"""Own device time per training step of every op outside the attention, MLP,
LM head and loss, clip and optimizer scopes: the embedding, norms, residual
adds, loop control and ``dfl.gossip`` (at one node, the cast of the master
weights to the working copy). With the four other readers it adds up to
``step_device_ms``."""
import scopes


def read(ctx):
    return scopes.layer_ms_per_step(ctx, (scopes.GOSSIP, scopes.OTHER))
