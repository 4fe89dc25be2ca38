"""Device idle time per batch launch that the program's dispatch path
holds, in milliseconds: idle time in the traced window charged to the
innermost span (``programtrace.idle_by_span``) where that is
``serve.schedule``, ``serve.stack``, ``serve.transfer`` or
``serve.launch``, averaged over the devices, per ``serve.dispatch`` event
(device 0).  Nothing to read without those spans on the trace."""
import programtrace

NAMES = ("serve.schedule", "serve.stack", "serve.transfer", "serve.launch")


def read(ctx):
    return programtrace.idle_ms_per(ctx, NAMES, "serve.dispatch")
