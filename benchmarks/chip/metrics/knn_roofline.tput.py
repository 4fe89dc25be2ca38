"""Share of its roofline that k-NN graph construction reaches, in
percent: the least time the chip could take for the k-NN work of the
rows dispatched in the traced window (``workcount.knn_work`` at each
graph a task's ``work.knn`` lists, against ``peaks.json``), over the
device time of the operations whose name holds ``knn``.  Nothing to read
without such operations or shapes."""
import tracecalc
import workcount


def read(ctx):
    per_row = {}
    for task, spec in ctx.tasks.items():
        work = [workcount.knn_work(**g) for g in spec["work"].get("knn", [])]
        if work:
            per_row[task] = (sum(w[0] for w in work), sum(w[1] for w in work))
    lo, hi = tracecalc.window(ctx.events)
    ns = sum(tracecalc.kernel_ns(ops, "knn", lo, hi)[0]
             for ops in ctx.events["devices"].values())
    ops = nbytes = 0.0
    for s in ctx.spans:
        if s["name"] == "serve.dispatch" and s["args"].get("device", 0) == 0 \
                and s["args"].get("task") in per_row:
            o, b = per_row[s["args"]["task"]]
            ops += o * s["args"]["bucket"]
            nbytes += b * s["args"]["bucket"]
    if not ns or not ops:
        return None
    share, _ = tracecalc.roofline_share(
        ops, nbytes, ns / 1e9,
        ctx.peaks["bf16_flops_per_s"], ctx.peaks["hbm_bytes_per_s"])
    return share
