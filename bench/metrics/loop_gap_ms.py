"""Device idle time per step that the training loop's ``train/data_wait``
or ``train/dispatch`` spans cover, averaged over the cell's chips: the
gaps the loop leaves between steps (the program's spans as profiler
annotations, on the device ops' clock)."""
from bench import scopes


def read(ctx):
    return scopes.ctx_loop_gap_ms(ctx)
