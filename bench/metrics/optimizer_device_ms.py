"""Own device time per training step of the ops under ``dfl.clip`` and
``dfl.update``: the global-norm clip and the optimizer's update, in the
traced window."""
import scopes


def read(ctx):
    return scopes.layer_ms_per_step(ctx, (scopes.CLIP, scopes.UPDATE))
