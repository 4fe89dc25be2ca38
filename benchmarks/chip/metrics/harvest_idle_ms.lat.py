"""Device idle time per batch harvest that the program's harvest path
holds, in milliseconds: idle time in the traced window charged to the
innermost span (``programtrace.idle_by_span``) where that is
``serve.wait``, ``serve.copy`` or ``serve.unpack``, averaged over the
devices, per ``serve.harvest`` event (device 0).  Nothing to read without
those spans on the trace."""
import programtrace

NAMES = ("serve.wait", "serve.copy", "serve.unpack")


def read(ctx):
    return programtrace.idle_ms_per(ctx, NAMES, "serve.harvest")
