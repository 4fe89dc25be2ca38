"""Share of the measured window in which no operation ran on the device,
in percent, averaged over the chips (profiler trace), in a throughput
cell."""


def read(ctx):
    s = ctx.summary
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])
