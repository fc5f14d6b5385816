"""Device time per step of the train step's ops that no program scope
names, averaged over the cell's chips: what the scopes leave uncovered
(profiler trace, scopes from the step's HLO)."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, lambda s: s is None)
