"""Median of the program's ``serve.queue_wait_ms`` histogram. Untraced by
the program's own tracer, the server records latency minus batch time
(``server.py`` ``_complete``): the wait for dispatch, plus the padding
before the timed region and the completion work before this request woke."""


def read(ctx):
    return ctx.adapter.registry_summary().histogram("serve.queue_wait_ms").percentile(50)
