import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

No arrays are ever materialized — params, optimizer state, caches, and
batches are ShapeDtypeStructs; ``jit(...).lower(...).compile()`` proves the
sharding config is coherent (collectives partition, memory fits) and yields
``memory_analysis()`` / ``cost_analysis()`` + the partitioned HLO from which
the roofline terms (launch/roofline.py) are derived.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-1b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/]
"""
import argparse
import json
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.distributed import sharding as shd
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh, mesh_axes
from repro.models.transformer import (init_caches, init_lm, lm_decode_step,
                                      lm_prefill)
from repro.train.optim import QTensor, adamw
from repro.train.step import build_train_step

QUANTIZE_ABOVE = 30e9          # int8 Adam moments for >30B-param archs


# ----------------------------------------------------------------- specs ---
def input_specs(arch: str, shape: str):
    """ShapeDtypeStruct stand-ins for every model input of a cell."""
    cfg = configs.get(arch)
    sh = configs.SHAPES[shape]
    B, S = sh["global_batch"], sh["seq_len"]
    kind = sh["kind"]
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    if kind == "train":
        if cfg.embed_inputs:
            return {"tokens": tok, "labels": tok}
        return {"embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                               jnp.dtype(cfg.dtype)),
                "labels": tok}
    if kind == "prefill":
        if cfg.embed_inputs:
            return {"tokens": tok}
        return {"embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                               jnp.dtype(cfg.dtype))}
    # decode: one new token against a seq_len cache
    return {"tokens": jax.ShapeDtypeStruct((B,), jnp.int32),
            "length": jax.ShapeDtypeStruct((), jnp.int32)}


def _opt_specs(pspecs, opt_shapes, mesh):
    """Optimizer-state specs: fp32 moments follow the param spec; int8
    QTensor moments keep the param spec on codes (same rank; last dim is
    padded to a multiple of 256 so every axis still divides) and drop the
    last-dim axis on scales."""
    def per_leaf(mleaf, pspec):
        if isinstance(mleaf, QTensor):
            rank = len(mleaf.codes.shape)
            full = list(tuple(pspec)) + [None] * (rank - len(tuple(pspec)))
            return QTensor(P(*full), P(*full[:-1], None))
        return pspec

    m = jax.tree.map(per_leaf, opt_shapes["m"], pspecs,
                     is_leaf=lambda x: isinstance(x, QTensor))
    v = jax.tree.map(per_leaf, opt_shapes["v"], pspecs,
                     is_leaf=lambda x: isinstance(x, QTensor))
    return {"step": P(), "m": m, "v": v}


# ------------------------------------------------------------------ cell ---
def lower_cell(arch: str, shape: str, *, multi_pod: bool = False,
               remat: bool = True, microbatches: int = 1,
               moe_path: str = "auto", extra_tag: str = ""):
    """Lower + compile one (arch, shape, mesh) cell; return analysis dict."""
    cfg = configs.get(arch)
    sh = configs.SHAPES[shape]
    if shape == "long_500k" and not cfg.subquadratic:
        return {"arch": arch, "shape": shape, "status": "n/a",
                "reason": "full-attention arch; 500k decode has no "
                          "sub-quadratic structure (DESIGN.md §5)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    dp, model_axis, fsdp = mesh_axes(mesh)
    kind = sh["kind"]
    t0 = time.time()

    pshapes = jax.eval_shape(partial(init_lm, cfg=cfg),
                             jax.random.PRNGKey(0))
    pspecs = shd.param_specs(pshapes, mesh, fsdp=fsdp, model=model_axis)
    pshard = shd.shardings(pspecs, mesh)
    ins = input_specs(arch, shape)
    bspecs = shd.batch_specs(kind, mesh, dp=dp, model=model_axis)

    with mesh:
        if kind == "train":
            quant = cfg.params_count() * 2 > QUANTIZE_ABOVE * 2
            opt = adamw(quantized=quant)
            oshapes = jax.eval_shape(opt.init, pshapes)
            ospecs = _opt_specs(pspecs, oshapes, mesh)
            oshard = shd.shardings(ospecs, mesh)
            in_b = {k: NamedSharding(mesh, bspecs[k]) for k in ins}
            step = build_train_step(
                cfg, opt, mesh=mesh, dp_axes=dp, model_axis=model_axis,
                remat=remat, microbatches=microbatches)
            jitted = jax.jit(
                step,
                in_shardings=(pshard, oshard, in_b),
                out_shardings=(pshard, oshard, None),
                donate_argnums=(0, 1))
            lowered = jitted.lower(pshapes, oshapes, ins)
        elif kind == "prefill":
            cshapes = jax.eval_shape(
                partial(init_caches, cfg, sh["global_batch"], sh["seq_len"]))
            cspecs = shd.cache_specs(cshapes, mesh, dp=dp, model=model_axis)
            cshard = shd.shardings(cspecs, mesh)
            in_b = {k: NamedSharding(mesh, bspecs[k]) for k in ins}

            def prefill_step(params, batch):
                return lm_prefill(
                    params, cfg, tokens=batch.get("tokens"),
                    embeds=batch.get("embeds"), max_len=sh["seq_len"],
                    impl="chunked", mesh=mesh, dp_axes=dp,
                    model_axis=model_axis)

            jitted = jax.jit(
                prefill_step,
                in_shardings=(pshard, in_b),
                out_shardings=(NamedSharding(mesh, P(dp, None)), cshard,
                               None))
            lowered = jitted.lower(pshapes, ins)
        else:  # decode
            cshapes = jax.eval_shape(
                partial(init_caches, cfg, sh["global_batch"], sh["seq_len"]))
            cspecs = shd.cache_specs(cshapes, mesh, dp=dp, model=model_axis)
            cshard = shd.shardings(cspecs, mesh)
            B = sh["global_batch"]
            tok_spec = P(dp) if B % shd._axsize(mesh, dp) == 0 else P()

            def decode_step(params, caches, tokens, length):
                return lm_decode_step(params, cfg, tokens, caches, length,
                                      mesh=mesh, dp_axes=dp,
                                      model_axis=model_axis)

            jitted = jax.jit(
                decode_step,
                in_shardings=(pshard, cshard,
                              NamedSharding(mesh, tok_spec), None),
                out_shardings=(NamedSharding(mesh, tok_spec), cshard),
                donate_argnums=(1,))
            lowered = jitted.lower(pshapes, cshapes, ins["tokens"],
                                   ins["length"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = hlo_analysis.collective_bytes(hlo)
    pc = hlo_analysis.program_costs(hlo)      # trip-count weighted
    n_dev = mesh.devices.size
    result = {
        "arch": arch, "shape": shape, "kind": kind, "status": "ok",
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "multi_pod": multi_pod, "devices": int(n_dev),
        "remat": remat, "microbatches": microbatches, "tag": extra_tag,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_device": pc["flops"],
        "bytes_per_device": pc["bytes"],
        "xla_cost_analysis": {            # unweighted cross-check
            "flops": cost.get("flops", 0.0),
            "bytes_accessed": cost.get("bytes accessed", 0.0)},
        "collective_bytes_per_device": coll,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", 0),
        },
        "params": cfg.params_count(),
        "active_params": cfg.active_params_count(),
    }
    return result


# ------------------------------------------------------------------ main ---
def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--baseline", action="store_true",
                    help="paper-faithful baseline: scan attention (stacked "
                         "residuals), no activation sharding constraints, "
                         "1-D gathered MoE")
    args = ap.parse_args(argv)
    if args.baseline:
        os.environ["REPRO_NO_WSC"] = "1"
        os.environ["REPRO_ATTN_IMPL"] = "chunked_scan"
        os.environ["REPRO_MOE_1D"] = "1"

    os.makedirs(args.out, exist_ok=True)
    cells = configs.cells(include_na=True) if args.all else \
        [(args.arch, args.shape)]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    n_ok = n_na = n_fail = 0
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}__{shape}__{'pod2' if mp else 'pod1'}"
            tag += f"__{args.tag}" if args.tag else ""
            out_path = os.path.join(args.out, tag + ".json")
            if os.path.exists(out_path):
                print(f"[skip] {tag} (exists)", flush=True)
                continue
            print(f"[cell] {tag} ...", flush=True)
            try:
                res = lower_cell(arch, shape, multi_pod=mp,
                                 remat=not args.no_remat,
                                 microbatches=args.microbatches,
                                 extra_tag=args.tag)
            except Exception as e:               # noqa: BLE001
                res = {"arch": arch, "shape": shape, "status": "fail",
                       "multi_pod": mp, "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc()[-4000:]}
            with open(out_path, "w") as f:
                json.dump(res, f, indent=1)
            st = res["status"]
            n_ok += st == "ok"
            n_na += st == "n/a"
            n_fail += st == "fail"
            msg = res.get("error", "")[:200]
            print(f"  -> {st} compile={res.get('compile_s', '-')}s "
                  f"flops/dev={res.get('flops_per_device', 0):.3e} {msg}",
                  flush=True)
    print(f"done: ok={n_ok} n/a={n_na} fail={n_fail}", flush=True)
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
