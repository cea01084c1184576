"""95th percentile of the time a request waited in the queue,
``RequestRecord.wait_s`` (ms)."""


def read(ctx):
    return ctx.queue_wait_p95_ms()
