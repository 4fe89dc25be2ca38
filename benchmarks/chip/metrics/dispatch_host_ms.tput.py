"""Mean host time of one batch launch, in milliseconds: the duration of
the program's ``serve.dispatch`` spans in the traced window (stacking the
inputs, their transfer and the program's launch)."""


def read(ctx):
    durs = [s["dur"] / 1e3 for s in ctx.spans
            if s["name"] == "serve.dispatch"
            and s["args"].get("device", 0) == 0]
    return sum(durs) / len(durs) if durs else None
