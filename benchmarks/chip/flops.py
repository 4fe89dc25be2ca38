"""FLOPs per request of a configuration, from its plain reference.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/flops.py dgcnn

Lowers the reference's forward pass for one request at the published
shapes (``precision="highest"``; ``work.flops_form`` in the configuration
may ask the reference for a form of the same function with less work)
and prints XLA's cost analysis of it: the operations a request needs,
whatever the program does to compute them.  The number goes into the
configuration file as ``work.flops_per_request``, with this command
beside it.
"""
from __future__ import annotations

import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent


def flops_per_request(name: str) -> float:
    import harness
    cfg = harness.load_json(HERE / "configs" / f"{name}.json")
    ref = harness.load_module(HERE / cfg["reference"])
    import jax
    params = ref.init_params(0, **cfg["sizes"])
    inputs = {k: jax.ShapeDtypeStruct((1, *v["shape"]), "float32")
              for k, v in cfg["inputs"].items()}
    form = cfg["work"].get("flops_form", {})
    fn = jax.jit(lambda **x: ref.batched(params, **form)(**x))
    return float(fn.lower(**inputs).cost_analysis()["flops"])


if __name__ == "__main__":
    sys.path.insert(0, str(HERE))
    print(json.dumps({"config": sys.argv[1],
                      "flops_per_request": flops_per_request(sys.argv[1])}))
