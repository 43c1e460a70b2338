"""Load generator: 99th percentile of how late each request due in the
window was sent (send time minus due time, host clock), in ms."""
from bench.readers import due_in_window


def read(ctx):
    late = [r.sent - r.due for r in due_in_window(ctx)]
    return 1e3 * ctx.yardstick.percentile(late, 99) if late else None
