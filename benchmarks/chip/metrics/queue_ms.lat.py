"""Median time a request waits in the scheduler's queue, in
milliseconds: ``queued_ms`` of the program's ``request`` spans in the
traced window."""
import statistics


def read(ctx):
    q = [s["args"]["queued_ms"] for s in ctx.spans
         if s["name"] == "request"]
    return statistics.median(q) if q else None
