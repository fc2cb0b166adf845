import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512 " \
    + os.environ.get("XLA_FLAGS", "")
# The two lines above MUST run before any jax import: jax locks the device
# count on first init. Everything below is ordinary code.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

A compile rehearsal on fake host devices: for each cell we build
abstract (ShapeDtypeStruct) params / optimizer state / caches, attach
NamedShardings, ``.lower().compile()`` the step, and record
``memory_analysis()`` / ``cost_analysis()`` / parsed collective bytes
and the cell's roofline terms into a JSON results file. Nothing runs:
the numbers are the compiler's and the roofline model's, not a
device's.

  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen1.5-0.5b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both \
      --out results/dryrun.json
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax

from repro.configs import SHAPES, all_cells, get_arch, get_shape
from repro.dist.context import make_dist
from repro.dist.sharding import sanitize_specs, tree_shardings
from repro.launch.mesh import make_production_mesh, make_test_mesh
from repro.models.api import build_model
from repro.roofline import analysis as roofline
from repro.roofline import hlo_cost
from repro.train.loop import init_opt_state, jit_train_step
from repro.train.optimizer import OptConfig

# tokens-per-device memory pressure -> grad accumulation (the batch is
# unchanged, microbatches scan sequentially)
GRAD_ACCUM = {
    "chameleon-34b": 8,
    "codeqwen1.5-7b": 4,
    "qwen1.5-0.5b": 1,
    "stablelm-12b": 4,
    "starcoder2-15b": 4,
    "zamba2-2.7b": 1,
    "deepseek-v3-671b": 16,
    "grok-1-314b": 8,
    "whisper-large-v3": 2,
    "rwkv6-3b": 1,
}


def _mesh(kind: str):
    if kind == "single":
        return make_production_mesh(multi_pod=False)
    if kind == "multi":
        return make_production_mesh(multi_pod=True)
    return make_test_mesh()


def _opt_cfg(arch: str) -> OptConfig:
    big = arch in ("deepseek-v3-671b", "grok-1-314b")
    return OptConfig(state_dtype="bfloat16" if big else "float32")


DIST_KEYS = ("fsdp", "seq_parallel", "ep_over_dp", "zero1")


def lower_cell(arch: str, shape_name: str, mesh_kind: str,
               overrides: dict | None = None):
    """Returns (lowered, compiled, meta) for one cell.

    overrides: ArchConfig fields, plus DistContext knobs (fsdp,
    seq_parallel, ep_over_dp, zero1) and 'grad_accum'."""
    overrides = dict(overrides or {})
    dist_kw = {k: overrides.pop(k) for k in DIST_KEYS if k in overrides}
    ga_override = overrides.pop("grad_accum", None)
    cfg = get_arch(arch)
    if overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **overrides)
    shape = get_shape(shape_name)
    mesh = _mesh(mesh_kind)
    dist = make_dist(mesh, **dist_kw)
    model = build_model(cfg, dist)
    abstract_params = model.abstract_params()
    pspecs = sanitize_specs(abstract_params, model.param_specs(), mesh)
    params_sh = jax.tree_util.tree_map(lambda s: dist.sharding(s), pspecs,
                                       is_leaf=lambda s: hasattr(s, "index"))
    in_structs, in_specs = model.input_specs(shape)
    in_specs = sanitize_specs(in_structs, in_specs, mesh)

    with mesh:
        if shape.kind == "train":
            ga = ga_override if ga_override is not None else GRAD_ACCUM[arch]
            step = jit_train_step(model, _opt_cfg(arch), grad_accum=ga,
                                  batch_specs=in_specs, donate=False)
            opt_abstract = jax.eval_shape(
                lambda p: init_opt_state(p, _opt_cfg(arch)), abstract_params)
            state = {"params": abstract_params, "opt": opt_abstract}
            lowered = step.lower(state, in_structs)
        elif shape.kind == "prefill":
            cache_abs = jax.eval_shape(
                lambda p, b: model.init_cache(p, b, shape.global_batch,
                                              shape.seq_len),
                abstract_params, in_structs)
            cache_sh = tree_shardings(dist, cache_abs, model.cache_specs())
            fn = jax.jit(model.prefill,
                         in_shardings=(params_sh, tree_shardings(
                             dist, in_structs, in_specs), cache_sh))
            lowered = fn.lower(abstract_params, in_structs, cache_abs)
        else:  # decode
            cache_abs = jax.eval_shape(
                lambda p, b: model.init_cache(p, b, shape.global_batch,
                                              shape.seq_len),
                abstract_params,
                _frames_stub(model, shape))
            cache_sh = tree_shardings(dist, cache_abs, model.cache_specs())
            fn = jax.jit(model.decode_step,
                         in_shardings=(params_sh, cache_sh,
                                       dist.sharding(in_specs["tokens"]),
                                       dist.sharding(in_specs["lengths"])))
            lowered = fn.lower(abstract_params, cache_abs,
                               in_structs["tokens"], in_structs["lengths"])
        t0 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t0
    return lowered, compiled, {"mesh_devices": mesh.size,
                               "compile_s": compile_s, "shape": shape,
                               "cfg": cfg}


def _frames_stub(model, shape):
    if model.family != "audio":
        return {"tokens": jax.ShapeDtypeStruct((shape.global_batch, 1),
                                               jax.numpy.int32)}
    st, _ = model.input_specs(shape)
    return st


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             overrides: dict | None = None) -> dict:
    t0 = time.time()
    try:
        lowered, compiled, meta = lower_cell(arch, shape_name, mesh_kind,
                                             overrides)
    except Exception as e:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "error", "error": f"{type(e).__name__}: {e}",
                "trace": traceback.format_exc()[-2000:]}
    xla_cost = hlo_cost.xla_cost_analysis(compiled)
    mem = compiled.memory_analysis()
    mem_d = {k: int(getattr(mem, k)) for k in
             ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes")
             if hasattr(mem, k)}
    hlo = compiled.as_text()
    totals = hlo_cost.analyze(hlo, default_group=meta["mesh_devices"])
    cfg, shape = meta["cfg"], meta["shape"]
    opt_b = 4 if arch in ("deepseek-v3-671b", "grok-1-314b") else 8
    floor = roofline.memory_floor_bytes(cfg, shape, meta["mesh_devices"],
                                        meta["mesh_devices"],
                                        opt_bytes_per_param=opt_b)
    rf = roofline.summarize(
        arch, shape_name, mesh_kind, meta["mesh_devices"],
        {"flops": totals.flops, "bytes accessed": totals.bytes},
        totals, roofline.model_flops(cfg, shape), floor_bytes=floor)
    return {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "status": "ok",
        "chips": meta["mesh_devices"],
        "compile_s": round(meta["compile_s"], 1),
        "total_s": round(time.time() - t0, 1),
        "memory": mem_d,
        "xla_cost": {k: xla_cost[k] for k in ("flops", "bytes accessed")
                     if k in xla_cost},
        "collectives": totals.to_dict(),
        "roofline": rf.to_dict(),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both", "test"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="results/dryrun.json")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    if args.all:
        cells = [(a, s) for a, s, runnable in all_cells() if runnable]
    else:
        from repro.configs import cell_is_runnable, get_arch as _ga
        shapes = [args.shape] if args.shape else list(SHAPES)
        cells = [(args.arch, s) for s in shapes
                 if cell_is_runnable(_ga(args.arch), get_shape(s))]

    out_path = Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    results = {}
    if out_path.exists():
        results = json.loads(out_path.read_text())

    for mesh_kind in meshes:
        for arch, shape_name in cells:
            key = f"{arch}|{shape_name}|{mesh_kind}"
            if results.get(key, {}).get("status") == "ok":
                print(f"[skip cached] {key}")
                continue
            print(f"[dry-run] {key} ...", flush=True)
            res = run_cell(arch, shape_name, mesh_kind)
            results[key] = res
            out_path.write_text(json.dumps(results, indent=1))
            st = res["status"]
            extra = (f" compile={res['compile_s']}s "
                     f"flops/dev={res['roofline']['hlo_gflops']:.1f}G "
                     f"bottleneck={res['roofline']['bottleneck']}"
                     if st == "ok" else res.get("error", ""))
            print(f"  -> {st}{extra}", flush=True)

    bad = [k for k, v in results.items() if v.get("status") != "ok"]
    print(f"\n{len(results) - len(bad)}/{len(results)} cells ok")
    for k in bad:
        print("FAILED:", k, results[k].get("error"))


if __name__ == "__main__":
    main()
