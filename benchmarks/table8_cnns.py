"""Paper Table VIII / XI analogue: standalone CNNs c1–c5. Modelled
GCV-Turbo throughput vs the paper's reported images/second."""
from __future__ import annotations

from benchmarks.common import compile_task, emit, plan_latency_s
from repro import gcv
from repro.gnncv import cnn_zoo

PAPER_THROUGHPUT = {"c1_alexnet": 512.9, "c2_resnet50": 58.8,
                    "c3_resnet101": 46.5, "c4_vgg16": 254.7,
                    "c5_vgg19": 127.3}


def build_all():
    return {
        "c1_alexnet": cnn_zoo.alexnet(),
        "c2_resnet50": cnn_zoo.resnet(50),
        "c3_resnet101": cnn_zoo.resnet(101),
        "c4_vgg16": cnn_zoo.vgg(16),
        "c5_vgg19": cnn_zoo.vgg(19),
    }


def run():
    rows = []
    for name, g in build_all().items():
        plan = compile_task(g, target="fpga")
        lat = plan_latency_s(plan)
        thr = 1.0 / lat
        paper = PAPER_THROUGHPUT[name]
        rows.append((name, f"{lat*1e3:.3f}", f"{thr:.1f}", f"{paper:.1f}",
                     f"{thr/paper:.2f}"))
    emit(rows, ["model", "modelled_latency_ms", "modelled_img_per_s",
                "paper_img_per_s", "ratio_model/paper"])
    return rows


if __name__ == "__main__":
    gcv.use_compile_cache()
    run()
