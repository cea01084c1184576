"""Host stacking time per batch, ``stack_busy_s`` of the engine (ms)."""


def read(ctx):
    return ctx.stack_ms()
