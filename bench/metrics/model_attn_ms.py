"""Device time per step of the train step's ops whose innermost program
scope is ``model/attn`` (pre-norm, QKV, RoPE, blockwise attention,
out-projection, residual add; forward, recompute and backward), averaged
over the cell's chips (profiler trace, scopes from the step's HLO)."""
from bench import scopes


def read(ctx):
    return scopes.scope_ms(ctx, lambda s: s == "model/attn")
