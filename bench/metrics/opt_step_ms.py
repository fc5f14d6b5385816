"""Device time per step of the whole optimizer: every op of the train
step whose innermost program scope starts with ``opt/`` (its Pallas
kernels, the residual, gathers, orientation, the full-rank AdamW),
averaged over the cell's chips (profiler trace, scopes from the step's
HLO)."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, lambda s: s is not None
                           and s.startswith("opt/"))
