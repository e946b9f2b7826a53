import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the production mesh ((16,16) single-pod / (2,16,16) multi-pod),
  2. builds the step function for the shape kind (train/prefill/serve),
  3. lowers against ShapeDtypeStruct inputs (no allocation), compiles,
  4. prints memory_analysis() (fits-per-device proof) and cost_analysis(),
  5. parses the optimized HLO for collective bytes (roofline term 3),
  6. writes a JSON record to experiments/dryrun/.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all --multi-pod both
"""

import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import SHAPES, shape_applicable
from repro.configs.registry import get_config, list_configs
from repro.distributed.sharding import ShardingRules, strip_pod
from repro.launch.mesh import make_production_mesh
from repro.models.io import input_specs
from repro.models.params import init_params
from repro.train.optimizer import AdamWConfig, adamw_init, opt_state_specs
from repro.train.steps import (
    batch_spec_tree,
    make_prefill_step,
    make_serve_step,
    make_train_step,
)
from repro.utils.hlo import analyze_hlo

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

# Per-arch optimizer state dtype (memory fit on the single pod, DESIGN.md).
STATE_DTYPE = {
    "jamba-1.5-large-398b": "int8",
    "qwen2.5-32b": "bf16",
    "llama-3.2-vision-11b": "bf16",
    "granite-8b": "bf16",
}


def abstract_state(cfg, mesh, rules, opt_cfg, serving: bool = False):
    """Params/opt-state as ShapeDtypeStructs + matching spec trees —
    no 398B allocation ever happens.  Serving stores params in bf16
    (there is no optimizer to need fp32 masters)."""
    box = {}

    def capture(key):
        p, s = init_params(cfg, key, rules, mesh.shape.get("model", 16))
        box["specs"] = s
        if serving:
            p = jax.tree.map(
                lambda x: x.astype(jnp.bfloat16)
                if jnp.issubdtype(x.dtype, jnp.floating)
                else x,
                p,
            )
        return p

    params_sds = jax.eval_shape(capture, jax.random.key(0))
    param_specs = box["specs"]
    opt_sds = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params_sds)
    opt_specs = opt_state_specs(params_sds, param_specs, opt_cfg)
    return params_sds, param_specs, opt_sds, opt_specs


def _strip(spec_tree, mesh):
    """Drop pod axis from spec trees when the mesh has none."""
    if "pod" in mesh.axis_names:
        return spec_tree

    def fix(spec):
        entries = []
        for e in spec:
            if isinstance(e, tuple):
                kept = tuple(a for a in e if a != "pod")
                entries.append(kept if len(kept) > 1 else (kept[0] if kept else None))
            else:
                entries.append(None if e == "pod" else e)
        return P(*entries)

    return jax.tree.map(fix, spec_tree, is_leaf=lambda x: isinstance(x, P))


def _sh(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def lower_cell(arch: str, shape_name: str, multi_pod: bool, kv_chunk: int = 2048,
               cast_before_scan: bool = False, serve_tp_only: bool = False,
               microbatches: int = 1, auto_policy: bool = False,
               kv_int8: bool = False, tag_suffix: str = ""):
    """Lower + compile one cell; returns the result record."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "multi_pod": multi_pod,
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = strip_pod(ShardingRules(), mesh)
    n_batch_devs = mesh.shape.get("pod", 1) * mesh.shape.get("data", 1)
    if shape.global_batch % n_batch_devs != 0:
        from repro.distributed.sharding import drop_batch_axes

        rules = drop_batch_axes(rules)
    # TP-only serving placement only when bf16 params fit comfortably next
    # to the KV cache when replicated over 'data' (<= ~4 GiB/device).
    model_axis = mesh.shape.get("model", 1)
    params_fit_tp = cfg.param_count() * 2 / model_axis <= 2 * 2**30
    if serve_tp_only and shape.kind in ("prefill", "decode") and params_fit_tp:
        from repro.distributed.sharding import tp_only_params

        rules = tp_only_params(rules)
    if auto_policy and shape.kind == "train":
        from repro.distributed.policy import apply_policy

        rules = apply_policy(cfg, mesh, rules, global_batch=shape.global_batch)
    opt_cfg = AdamWConfig(state_dtype=STATE_DTYPE.get(arch, "fp32"))

    t0 = time.time()
    use_int8 = kv_int8 and cfg.family in ("dense", "moe")
    specs_in = input_specs(cfg, shape, kv_int8=use_int8)
    batch_specs = _strip(
        batch_spec_tree(cfg, shape, ShardingRules(), mesh, kv_int8=use_int8), mesh
    )
    batch_sh = _sh(mesh, batch_specs)

    if shape.kind == "train":
        step, model = make_train_step(
            cfg, mesh, opt_cfg, rules=rules, remat=True, kv_chunk=kv_chunk,
            cast_before_scan=cast_before_scan, microbatches=microbatches,
        )
        params_sds, p_specs, opt_sds, o_specs = abstract_state(
            cfg, mesh, rules, opt_cfg
        )
        p_sh, o_sh = _sh(mesh, p_specs), _sh(mesh, o_specs)
        jitted = jax.jit(
            step,
            in_shardings=(p_sh, o_sh, batch_sh),
            out_shardings=(p_sh, o_sh, None),
            donate_argnums=(0, 1),
        )
        lowered = jitted.lower(params_sds, opt_sds, specs_in)
    elif shape.kind == "prefill":
        step, model = make_prefill_step(cfg, mesh, kv_chunk=kv_chunk, rules=rules,
                                        cast_before_scan=cast_before_scan)
        params_sds, p_specs, _, _ = abstract_state(cfg, mesh, rules, opt_cfg,
                                                     serving=True)
        p_sh = _sh(mesh, p_specs)
        jitted = jax.jit(step, in_shardings=(p_sh, batch_sh))
        lowered = jitted.lower(params_sds, specs_in)
    else:  # decode / serve
        step, model = make_serve_step(cfg, mesh, kv_chunk=max(kv_chunk, 4096),
                                      rules=rules, kv_int8=kv_int8,
                                      cast_before_scan=cast_before_scan)
        params_sds, p_specs, _, _ = abstract_state(cfg, mesh, rules, opt_cfg,
                                                     serving=True)
        p_sh = _sh(mesh, p_specs)
        jitted = jax.jit(
            step,
            in_shardings=(p_sh, batch_sh),
            out_shardings=batch_sh,
            donate_argnums=(1,),
        )
        lowered = jitted.lower(params_sds, specs_in)

    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()  # PER-DEVICE (SPMD module stats)
    cost = compiled.cost_analysis()
    hlo_cost = analyze_hlo(compiled.as_text())  # loop-aware, per-device

    n_chips = 1
    for _, v in mesh.shape.items():
        n_chips *= v

    rec = {
        "arch": arch,
        "shape": shape_name,
        "multi_pod": multi_pod,
        "status": "ok",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        # raw XLA numbers (per device, while-bodies counted once):
        "xla_flops_body_once": float(cost.get("flops", 0.0)),
        "xla_bytes_body_once": float(cost.get("bytes accessed", 0.0)),
        # loop-aware walker numbers (per device):
        "flops_per_device": hlo_cost.flops,
        "hbm_bytes_proxy_per_device": hlo_cost.hbm_bytes_proxy,
        "collective_bytes_per_device": hlo_cost.collective_bytes,
        "collective_bytes_by_op": hlo_cost.collective_by_op,
        "collective_counts": hlo_cost.collective_counts,
        "memory_per_device": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    peak = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    rec["memory_per_device"]["peak_estimate_bytes"] = peak
    fits = peak <= 16 * 2**30
    rec["fits_16gib_hbm"] = bool(fits)
    print(f"[dryrun] {arch} x {shape_name} x {'2pod' if multi_pod else '1pod'}: "
          f"compile {t_compile:.0f}s | peak/device {peak / 2**30:.2f} GiB "
          f"({'FITS' if fits else 'OVER'}) | flops/dev {hlo_cost.flops:.3e} | "
          f"coll/dev {hlo_cost.collective_bytes / 2**30:.3f} GiB")
    print("  memory_analysis:", mem)
    interesting = {k: v for k, v in cost.items()
                   if k in ("flops", "bytes accessed", "transcendentals")}
    print("  cost_analysis:", interesting)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--multi-pod", choices=("true", "false", "both"), default="false")
    ap.add_argument("--kv-chunk", type=int, default=2048)
    ap.add_argument("--out", default=str(OUT_DIR))
    ap.add_argument("--cast-before-scan", action="store_true",
                    help="perf: bf16-cast stacked params outside the scan")
    ap.add_argument("--serve-tp-only", action="store_true",
                    help="perf: serving params TP-sharded, data-replicated")
    ap.add_argument("--microbatches", type=int, default=1,
                    help="perf: gradient accumulation slices (train shapes)")
    ap.add_argument("--kv-int8", action="store_true",
                    help="perf: int8 KV cache with per-(token,head) scales "
                         "(decode shapes, dense/moe families)")
    ap.add_argument("--auto-policy", action="store_true",
                    help="perf: per-arch parallelism policy (replicate block "
                         "weights for TP-starved models)")
    ap.add_argument("--tag", default="", help="suffix for output JSON names")
    args = ap.parse_args()

    archs = list_configs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    pods = {"true": [True], "false": [False], "both": [False, True]}[args.multi_pod]

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in pods:
                tag = f"{arch}_{shape}_{'2pod' if mp else '1pod'}{args.tag}"
                try:
                    rec = lower_cell(
                        arch, shape, mp, kv_chunk=args.kv_chunk,
                        cast_before_scan=args.cast_before_scan,
                        serve_tp_only=args.serve_tp_only,
                        microbatches=args.microbatches,
                        auto_policy=args.auto_policy,
                        kv_int8=args.kv_int8,
                    )
                except Exception as e:  # noqa: BLE001 — report, keep going
                    rec = {
                        "arch": arch, "shape": shape, "multi_pod": mp,
                        "status": "error", "error": repr(e),
                        "traceback": traceback.format_exc()[-4000:],
                    }
                    failures.append(tag)
                    print(f"[dryrun] FAIL {tag}: {e!r}")
                (out_dir / f"{tag}.json").write_text(json.dumps(rec, indent=2))
    if failures:
        print(f"[dryrun] {len(failures)} FAILURES: {failures}")
        raise SystemExit(1)
    print("[dryrun] all cells OK")


if __name__ == "__main__":
    main()
