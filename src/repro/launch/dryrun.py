import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The two lines above MUST stay first: jax locks the device count on first
initialisation, and the production meshes need 512 placeholder devices.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch internlm2-20b \
      --shape train_4k [--multi-pod] [--out artifacts/]
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""
import argparse
import json
import sys
import time
import traceback

import jax

from repro.configs.base import ARCH_IDS, SHAPES, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.specs import step_args_abstract
from repro.launch import hlo_analysis
from repro.optim.adamw import AdamWConfig
from repro.training.train_state import (make_decode_step, make_prefill_step,
                                        make_train_step)


def cell_is_skipped(arch: str, shape_name: str) -> str:
    """Returns a reason string if the cell is skipped, else ''."""
    cfg = get_config(arch)
    if shape_name == "long_500k" and not cfg.subquadratic:
        return ("full-attention arch: 500k decode requires sub-quadratic "
                "attention (see DESIGN.md §Arch-applicability)")
    return ""


VARIANTS = {
    "baseline": {},
    "tp_sp": {"tp_sp": True},
    "pad_heads": {"pad_attn_heads": True},
    "tp_sp+pad": {"tp_sp": True, "pad_attn_heads": True},
    "moe_int8": {"moe_a2a_int8": True},
    "remat_dots": {"remat": "dots"},
    "flash_full": {"attn_impl": "full"},   # pre-flash paper-faithful naive
    "tp_sp+moe_int8": {"tp_sp": True, "moe_a2a_int8": True},
    "tp_sp+remat_dots": {"tp_sp": True, "remat": "dots"},
}


def lower_cell(arch: str, shape_name: str, mesh, variant: str = "baseline"
               ) -> tuple:
    """Returns (lowered, compiled) for one cell."""
    cfg = get_config(arch).replace(**VARIANTS[variant])
    shape = SHAPES[shape_name]
    if shape.kind == "train" and cfg.micro_steps == 1 and cfg.d_model >= 3584:
        # auto gradient-accumulation: large models need 2 microbatches to fit
        # the 16 GB/chip activation budget at global_batch=256 x 4k
        cfg = cfg.replace(micro_steps=2)
    args = step_args_abstract(cfg, shape, mesh)
    if shape.kind == "train":
        step = make_train_step(cfg, AdamWConfig(), mesh)
        donate = (0,)
    elif shape.kind == "prefill":
        step = make_prefill_step(cfg, mesh)
        donate = (2,)
    else:
        step = make_decode_step(cfg, mesh)
        donate = (2,)
    jitted = jax.jit(step, donate_argnums=donate)
    with mesh:
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    return lowered, compiled


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str = None,
             verbose: bool = True, variant: str = "baseline") -> dict:
    t0 = time.time()
    reason = cell_is_skipped(arch, shape_name)
    rec = {"arch": arch, "shape": shape_name, "variant": variant,
           "mesh": "2x16x16" if multi_pod else "16x16"}
    if reason:
        rec["status"] = "skipped"
        rec["reason"] = reason
        return rec
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        lowered, compiled = lower_cell(arch, shape_name, mesh, variant)
        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        if verbose:
            print(mem)
            print({k: v for k, v in cost.items()
                   if k in ("flops", "bytes accessed")})
        hlo = hlo_analysis.analyze_hlo(compiled.as_text(),
                                       n_devices=mesh.size)
        rec.update({
            "status": "ok",
            "compile_s": round(time.time() - t0, 1),
            "memory": {
                "argument_bytes": mem.argument_size_in_bytes,
                "output_bytes": mem.output_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "alias_bytes": mem.alias_size_in_bytes,
            },
            "xla_cost": {"flops": cost.get("flops", 0.0),
                         "bytes_accessed": cost.get("bytes accessed", 0.0)},
            "hlo": hlo,
        })
    except Exception as e:  # noqa: BLE001 — sweep must record failures
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    rec["wall_s"] = round(time.time() - t0, 1)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = "" if variant == "baseline" else f"__{variant}"
        fn = os.path.join(out_dir,
                          f"{arch}__{shape_name}__{rec['mesh']}{suffix}.json")
        with open(fn, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--variant", default="baseline", choices=list(VARIANTS))
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells.append((args.arch, args.shape))

    ok = True
    for arch, shape in cells:
        rec = run_cell(arch, shape, args.multi_pod, args.out,
                       variant=args.variant)
        status = rec["status"]
        extra = ""
        if status == "ok":
            extra = (f"flops/dev={rec['hlo']['flops']:.3e} "
                     f"coll={rec['hlo']['collective_bytes']:.3e}B "
                     f"args={rec['memory']['argument_bytes']/2**30:.2f}GiB "
                     f"{rec['wall_s']}s")
        elif status == "error":
            ok = False
            extra = rec["error"][:200]
        print(f"[{status:7s}] {arch:24s} {shape:12s} {rec['mesh']:8s} {extra}",
              flush=True)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
