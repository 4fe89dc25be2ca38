"""Time the serving loop spent in runtime stalls, in milliseconds per
second of the traced window: the summed duration of the program's
``serve.stall`` events (a call into the runtime slower than the
program's ``STALL_MS``), 0.0 where there is none.  Nothing to read from a
program that counts no stalls."""
import programtrace


def read(ctx):
    return programtrace.stall_ms_per_s(ctx)
