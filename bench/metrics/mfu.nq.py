"""Model FLOPs served over executor-program device time at the bf16
peak (%)."""


def read(ctx):
    return ctx.mfu_pct()
