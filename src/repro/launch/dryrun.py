"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo.

MUST be run as a module entry point:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-3b --shape train_4k
The XLA_FLAGS line below must execute before jax initializes devices, which
is why it is the very first statement of the file.
"""
import os
os.environ["XLA_FLAGS"] = (os.environ.get("_EXTRA_XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512")

import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro import sharding as shard_rules                       # noqa: E402
from repro.configs.base import INPUT_SHAPES, get_config          # noqa: E402
from repro.launch import steps as steps_mod                      # noqa: E402
from repro.launch.mesh import make_production_mesh               # noqa: E402

# HLO dtype byte widths for the collective-bytes parse
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16, "s4": 1, "u4": 1, "f8e4m3": 1,
                "f8e5m2": 1}

_COLL_RE = re.compile(
    r"=\s+(?:\()?([a-z0-9]+)\[([0-9,]*)\][^=]*?"
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")

# how many token positions of `seq_len` a decode shape actually computes
SKIPS = {
    ("whisper-small", "long_500k"):
        "enc-dec ASR decoder: 500k-token decoder cache is out of family "
        "scope (max ctx 448 in the original); see DESIGN.md §5.",
}


def collective_bytes(hlo_text: str) -> dict:
    out = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
           "all-to-all": 0, "collective-permute": 0, "count": 0}
    for m in _COLL_RE.finditer(hlo_text):
        dt, dims, kind = m.group(1), m.group(2), m.group(3)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out[kind] += n * _DTYPE_BYTES[dt]
        out["count"] += 1
    out["total"] = sum(out[k] for k in
                       ("all-reduce", "all-gather", "reduce-scatter",
                        "all-to-all", "collective-permute"))
    return out


def pick_optimizer(cfg) -> str:
    """Adam states for <=50B-param actives; momentum above (HBM budget)."""
    return "momentum" if cfg.param_count() > 5e10 else "adam"


def run_one(arch: str, shape_name: str, multi_pod: bool, *,
            easter_on: bool = True, zero1: bool = False, unroll: bool = False,
            layout: str = "tp", moe_dense_passive: bool = False,
            serve_fsdp: bool = None, kv_quant: bool = False,
            save_dir: str = "experiments/dryrun", tag: str = "") -> dict:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    skip_key = (arch, shape_name)
    if skip_key in SKIPS:
        return {"arch": arch, "shape": shape_name, "skipped": SKIPS[skip_key]}

    mesh = make_production_mesh(multi_pod=multi_pod)
    import dataclasses
    if unroll:
        cfg = dataclasses.replace(cfg, scan_layers=False)
    if kv_quant:
        cfg = dataclasses.replace(cfg, kv_quant=True)
    easter = steps_mod.default_easter(cfg, enabled=easter_on)
    if moe_dense_passive:
        import dataclasses as _dc
        easter = _dc.replace(easter, moe_dense_passive=True)
    sys = steps_mod.make_system(cfg, easter)
    specs = steps_mod.input_specs(cfg, shape, sys)
    params = steps_mod._abstract_params(sys)

    t0 = time.time()
    with shard_rules.ambient_mesh(mesh, layout), jax.set_mesh(mesh):
        if shape.kind == "train":
            opt_name = pick_optimizer(cfg)
            _, opt_state = steps_mod.abstract_state(sys, opt_name)
            train_step, _ = steps_mod.build_train_step(sys, opt_name)
            in_sh, out_sh = steps_mod.train_shardings(
                sys, mesh, specs, params, opt_state, zero1=zero1,
                layout=layout)
            in_sh = steps_mod.to_shardings(mesh, in_sh)
            out_sh = steps_mod.to_shardings(mesh, out_sh)
            fn = jax.jit(train_step, in_shardings=in_sh,
                         out_shardings=out_sh, donate_argnums=(0, 1))
            lowered = fn.lower(params, opt_state, specs["batch"],
                               jax.ShapeDtypeStruct((), jnp.int32))
        elif shape.kind == "prefill":
            prefill = steps_mod.build_prefill_step(sys, shape)
            out_caches = jax.eval_shape(prefill, params, specs["batch"])[1]
            in_sh, out_sh = steps_mod.prefill_shardings(
                sys, mesh, specs, params, out_caches)
            in_sh = steps_mod.to_shardings(mesh, in_sh)
            out_sh = steps_mod.to_shardings(mesh, out_sh)
            fn = jax.jit(prefill, in_shardings=in_sh, out_shardings=out_sh)
            lowered = fn.lower(params, specs["batch"])
        else:  # decode
            serve = steps_mod.build_serve_step(sys, shape)
            in_sh, out_sh = steps_mod.serve_shardings(sys, mesh, specs,
                                                      params,
                                                      fsdp=serve_fsdp)
            args = [params, specs["batch"], specs["caches"], specs["pos"]]
            if "fe_list" in specs:
                args.append(specs["fe_list"])
            in_sh = steps_mod.to_shardings(mesh, in_sh)
            out_sh = steps_mod.to_shardings(mesh, out_sh)
            fn = jax.jit(serve, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=(2,))
            lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text())
    n_dev = mesh.devices.size
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(n_dev),
        "easter": bool(easter_on), "zero1": bool(zero1),
        "unroll": bool(unroll), "layout": layout,
        "moe_dense_passive": bool(moe_dense_passive),
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": coll,
        "params_active_party": int(cfg.param_count()),
        "params_active_party_active": int(cfg.active_param_count()),
        "memory": {
            "argument_size_bytes": int(getattr(mem, "argument_size_in_bytes",
                                               0)),
            "output_size_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_size_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "generated_code_size_bytes": int(
                getattr(mem, "generated_code_size_in_bytes", 0)),
        },
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
    }
    os.makedirs(save_dir, exist_ok=True)
    suffix = ("_pod2" if multi_pod else "") + ("_unroll" if unroll else "") + (f"_{tag}" if tag else "")
    path = os.path.join(save_dir, f"{arch}_{shape_name}{suffix}.json")
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    result["_path"] = path
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--no-easter", action="store_true")
    ap.add_argument("--zero1", action="store_true")
    ap.add_argument("--layout", default="tp", choices=["tp", "zero3"])
    ap.add_argument("--moe-dense-passive", action="store_true")
    ap.add_argument("--serve-fsdp", action="store_true")
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache (decode memory lever)")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll layer stacks for accurate cost_analysis")
    ap.add_argument("--tag", default="")
    ap.add_argument("--save-dir", default="experiments/dryrun")
    args = ap.parse_args()

    from repro.configs.base import list_archs
    archs = ([a for a in list_archs() if not a.startswith("easter")]
             if args.arch == "all" else args.arch.split(","))
    shapes = (list(INPUT_SHAPES) if args.shape == "all"
              else args.shape.split(","))
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                label = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
                try:
                    r = run_one(arch, shape, mp,
                                easter_on=not args.no_easter,
                                zero1=args.zero1, unroll=args.unroll,
                                layout=args.layout,
                                moe_dense_passive=args.moe_dense_passive,
                                serve_fsdp=args.serve_fsdp or None,
                                kv_quant=args.kv_quant,
                                save_dir=args.save_dir, tag=args.tag)
                    if "skipped" in r:
                        print(f"[SKIP] {label}: {r['skipped']}")
                        continue
                    print(f"[OK]   {label}: flops={r['flops']:.3e} "
                          f"coll={r['collective_bytes']['total']:.3e}B "
                          f"temp={r['memory']['temp_size_bytes']/2**30:.2f}GiB"
                          f" compile={r['compile_s']}s")
                except Exception as e:
                    failures += 1
                    print(f"[FAIL] {label}: {type(e).__name__}: {e}")
                    traceback.print_exc()
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
