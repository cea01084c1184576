"""Executor time per batch (copy in, run, copy out), ``exec_busy_s`` (ms)."""


def read(ctx):
    return ctx.exec_ms()
