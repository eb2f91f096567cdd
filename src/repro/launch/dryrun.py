import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.
#
# The two lines above MUST stay the first statements in this file — jax
# locks the device count at first initialization (dry-run contract).
#
# Usage:
#   PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b \
#       --shape train_4k [--multi-pod] [--rules fsdp_tp] [--out results/]
#   PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
#
# Each cell writes <out>/<arch>__<shape>__<mesh>[__<rules>].json with
# memory_analysis, cost_analysis, parsed HLO stats, and roofline terms.

import argparse
import glob
import json
import shutil
import tempfile
import time
import traceback

import jax

from repro import configs
from repro.configs import ALL_ARCHS, SHAPES
from repro.core import qad as qad_mod
from repro.core.qconfig import BF16
from repro.distributed import ctx as shd_ctx
from repro.distributed import sharding as shd
from repro.launch import hlo_analysis, roofline, specs
from repro.launch.mesh import make_production_mesh
from repro.models import get_model
from repro.optim import AdamW

# The chip the production mesh stands for: a 16x16 pod of TPU v5e.
TARGET_KIND = "TPU v5 lite"


def build_step(cfg, shape, qadcfg=None, weight_format="qdq"):
    """The jit-able function + abstract inputs for one cell.

    ``weight_format="packed"`` lowers serve steps against abstract
    ``PackedNVFP4`` weights through the GSPMD-shardable dequant-einsum
    backend — the dry-run then prices the 0.5625 B/param footprint.
    """
    import dataclasses
    model = get_model(cfg)
    qcfg = specs.recipe_qconfig(cfg)

    if shape.kind == "train":
        opt = AdamW(lr=1e-5, state_dtype="float32")
        step = qad_mod.make_train_step(model, cfg, qcfg, opt,
                                       qadcfg or qad_mod.QADConfig())
        return step, "train"

    sq = specs.serve_qconfig(cfg)
    if weight_format == "packed":
        sq = dataclasses.replace(sq, weight_format="packed",
                                 packed_backend="dequant")
    if shape.kind == "prefill":
        def prefill_step(params, batch):
            return model.prefill(cfg, params, batch, sq, s_max=shape.seq_len)
        return prefill_step, "prefill"

    def serve_step(params, cache, batch):
        return model.decode_step(cfg, params, cache, batch, sq)
    return serve_step, "decode"


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             rules_mode: str = "fsdp_tp", qadcfg=None,
             donate: bool = True, overrides: dict | None = None,
             weight_format: str = "qdq") -> dict:
    import dataclasses
    cfg = configs.get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    mesh_name = "2x16x16" if multi_pod else "16x16"
    n_chips = 512 if multi_pod else 256
    cell = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
            "rules": rules_mode, "kind": shape.kind,
            "variant": dict(overrides or {},
                            **({"chunked_loss": True} if qadcfg and
                               getattr(qadcfg, "use_chunked_loss", False)
                               else {}),
                            **({"weight_format": weight_format}
                               if weight_format != "qdq" else {}))}
    if shape.kind in ("prefill", "decode"):
        # analytic deployment pricing: packed 4-bit weights, FP8-vs-BF16 KV;
        # packed cells also price the TP partition of the production mesh
        # (model axis = 16) — per-device weight/KV bytes under resolve_packed
        cell["serve_memory"] = specs.serve_memory_report(
            cfg, shape, tp=(16 if weight_format == "packed" else 0))

    if shape_name in cfg.skip_shapes:
        cell["status"] = "SKIP"
        cell["reason"] = ("full-attention arch: 500k dense KV cache is "
                         "architecturally out of scope (DESIGN.md §4)")
        return cell

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = shd.make_rules(mesh, rules_mode)
    step, kind = build_step(cfg, shape, qadcfg, weight_format)

    dump_dir = tempfile.mkdtemp(prefix="xdump_")
    copts = {"xla_dump_to": dump_dir,
             "xla_dump_hlo_pass_re": "spmd-partitioning"}
    t0 = time.time()
    with jax.set_mesh(mesh), shd_ctx.use(mesh, rules):
        if kind == "train":
            state, batch = specs.train_inputs(cfg, shape, mesh, rules,
                                              AdamW(state_dtype="float32"))
            fn = jax.jit(step, donate_argnums=(0,) if donate else ())
            lowered = fn.lower(state, batch)
        elif kind == "prefill":
            params, _, batch = specs.serve_inputs(cfg, shape, mesh, rules,
                                                  weight_format)
            lowered = jax.jit(step).lower(params, batch)
        else:
            params, cache, batch = specs.serve_inputs(cfg, shape, mesh, rules,
                                                      weight_format)
            fn = jax.jit(step, donate_argnums=(1,) if donate else ())
            lowered = fn.lower(params, cache, batch)
        t1 = time.time()
        compiled = lowered.compile(copts)
        t2 = time.time()

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    # analyze the post-SPMD, pre-backend HLO (per-device shapes, original
    # scan trip counts — see hlo_analysis docstring)
    spmd_files = sorted(glob.glob(
        os.path.join(dump_dir, "*after_spmd-partitioning*.txt")))
    hlo = open(spmd_files[-1]).read() if spmd_files else compiled.as_text()
    stats = hlo_analysis.analyze_hlo(hlo, n_chips)
    rf = roofline.compute(cfg, shape, stats, n_chips, TARGET_KIND)
    shutil.rmtree(dump_dir, ignore_errors=True)

    cell.update({
        "status": "OK",
        "lower_s": round(t1 - t0, 2),
        "compile_s": round(t2 - t1, 2),
        "hlo_bytes": len(hlo),
        "memory": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "peak_bytes_per_device": int(ma.argument_size_in_bytes
                                         + ma.temp_size_in_bytes),
            "fits_hbm": bool(ma.argument_size_in_bytes + ma.temp_size_in_bytes
                             < roofline.hw(TARGET_KIND)["hbm_cap"]),
        },
        "cost_analysis": {"flops": float(ca.get("flops", 0.0)),
                          "bytes_accessed": float(ca.get("bytes accessed", 0.0))},
        "hlo_stats": stats,
        "roofline": rf.as_dict(),
        "n_params": cfg.n_params(),
        "n_params_active": cfg.n_params(active_only=True),
    })
    return cell


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ALL_ARCHS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--rules", default="fsdp_tp")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--chunked-loss", action="store_true",
                    help="use the fused chunked-vocab KL loss (perf iter)")
    ap.add_argument("--moe-dispatch", default=None,
                    choices=["global", "local"])
    ap.add_argument("--moe-shard", default=None, choices=["ep", "tp"])
    ap.add_argument("--remat", default=None, choices=["none", "dots", "full"])
    ap.add_argument("--weight-format", default="qdq",
                    choices=["qdq", "packed"],
                    help="packed: lower serve cells against abstract "
                    "PackedNVFP4 weights (4-bit deployment footprint)")
    ap.add_argument("--tag", default="", help="suffix for the output json")
    args = ap.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = ([(a, s) for a in ALL_ARCHS[:10] for s in SHAPES]
             if args.all else [(args.arch, args.shape)])
    qadcfg = (qad_mod.QADConfig(use_chunked_loss=True)
              if args.chunked_loss else None)

    overrides = {}
    if args.moe_dispatch:
        overrides["moe_dispatch"] = args.moe_dispatch
    if args.moe_shard:
        overrides["moe_shard"] = args.moe_shard
    if args.remat:
        overrides["remat"] = args.remat

    failures = 0
    for arch, shape in cells:
        tag = f"{arch}__{shape}__{'2x16x16' if args.multi_pod else '16x16'}"
        if args.rules != "fsdp_tp":
            tag += f"__{args.rules}"
        if args.chunked_loss:
            tag += "__chunkedkl"
        if args.weight_format != "qdq":
            tag += f"__{args.weight_format}"
        if args.tag:
            tag += f"__{args.tag}"
        path = os.path.join(args.out, tag + ".json")
        try:
            cell = run_cell(arch, shape, args.multi_pod, args.rules, qadcfg,
                            overrides=overrides or None,
                            weight_format=args.weight_format)
        except Exception as e:
            cell = {"arch": arch, "shape": shape, "status": "FAIL",
                    "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:]}
            failures += 1
        with open(path, "w") as f:
            json.dump(cell, f, indent=1)
        status = cell["status"]
        extra = ""
        if status == "OK":
            r = cell["roofline"]
            extra = (f" dom={r['dominant']} mfu={r['mfu']:.3f} "
                     f"compile={cell['compile_s']}s "
                     f"mem/dev={cell['memory']['peak_bytes_per_device']/2**30:.2f}GiB")
        elif status == "FAIL":
            extra = " " + cell["error"][:160]
        print(f"[{status}] {tag}{extra}", flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
