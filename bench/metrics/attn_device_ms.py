"""Own device time per training step of the ops under the model's
``attention`` scope (q/k/v/o projections, RoPE, KV expansion and the
query-block loop, forward, remat and backward), in the traced window."""
import scopes


def read(ctx):
    return scopes.layer_ms_per_step(ctx, (scopes.ATTENTION,))
