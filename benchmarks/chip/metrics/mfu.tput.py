"""The whole step's share of the chips' peak, in percent: the operations
a request needs (each task's ``work.flops_per_request``, from the plain
reference's cost analysis) times the requests of that task answered in
the traced window, over the window, chips and the bfloat16 peak of
``peaks.json``."""


def read(ctx):
    flops = sum(spec["work"]["flops_per_request"]
                * ctx.answered_by_task.get(task, 0)
                for task, spec in ctx.tasks.items())
    return 100.0 * flops / (ctx.window_s * ctx.chips
                            * ctx.peaks["bf16_flops_per_s"])
