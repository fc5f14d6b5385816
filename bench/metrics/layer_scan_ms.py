"""Device time per step of the layer scan's own work outside the blocks
(innermost program scope ``model/blocks``: slicing each layer's weights
out of the stack, stacking residuals and gradients), averaged over the
cell's chips (profiler trace, scopes from the step's HLO)."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, lambda s: s == "model/blocks")
