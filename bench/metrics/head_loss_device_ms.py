"""Own device time per training step of the ops under ``lm_head_loss``: the
final norm, the tied readout's logits and the cross-entropy, forward and
backward, in the traced window."""
import scopes


def read(ctx):
    return scopes.layer_ms_per_step(ctx, (scopes.HEAD_LOSS,))
