import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay first: jax locks the device count at first
init, and the production meshes need 512 placeholder host devices.

Per cell this script:
  1. builds the production mesh (16x16 pod / 2x16x16 multi-pod),
  2. lowers the right step (train_4k → train_step; prefill_32k →
     prefill_step; decode_32k / long_500k → serve_step) against
     ShapeDtypeStruct inputs with explicit in/out shardings,
  3. compiles, prints memory_analysis() (proves fit) and cost_analysis()
     (FLOPs/bytes for §Roofline), parses collective bytes from the HLO,
  4. applies the scan-body correction (XLA counts a while-loop body once —
     a 2-group unrolled twin isolates the per-group cost exactly),
  5. writes a JSON record to --out.

Usage:
  python -m repro.launch.dryrun --arch llama3_2_3b --shape train_4k
  python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
"""
import argparse
import dataclasses
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro import configs
from repro.launch.mesh import dp_axes, make_production_mesh
from repro.launch.roofline import model_flops, parse_collectives, roofline_terms
from repro.launch.shapes import cache_specs_shapes, input_specs
from repro.launch.sharding import (
    batch_shardings,
    cache_specs,
    opt_state_shardings,
    param_shardings,
)
from repro.models.pspec import activation_axes
from repro.models.steps import make_prefill_step, make_serve_step, make_train_step
from repro.models.transformer import init_params
from repro.optim.adam import AdamWConfig, init_opt_state


def _lower_one(cfg, shape: str, mesh, overrides: dict, *, unroll_scan: bool = False):
    """Lower + compile one step for `cfg` on `mesh`. Returns compiled."""
    seq, gb, kind = configs.SHAPES[shape]
    params_shape = jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0), jnp.bfloat16)
    )
    fsdp = () if overrides.get("serve_repl") else None
    p_shard = param_shardings(cfg, params_shape, mesh, fsdp=fsdp)
    specs = input_specs(cfg, shape)
    b_shard = batch_shardings(specs["batch"], mesh)

    with mesh, activation_axes(mesh, dp=dp_axes(mesh), tp="model",
                               sp=overrides.get("sp"), unroll_scan=unroll_scan,
                               ep_shard_map=overrides.get("ep_shard_map", False)):
        if kind == "train":
            # >100B params: bf16 optimizer states (see EXPERIMENTS.md §Dry-run)
            state_dtype = jnp.bfloat16 if cfg.num_params() > 1e11 else jnp.float32
            opt = AdamWConfig(state_dtype=state_dtype,
                              compress_grads=overrides.get("compress_grads"))
            opt_shape = jax.eval_shape(lambda: init_opt_state(params_shape, opt))
            o_shard = opt_state_shardings(p_shard, mesh)
            step = make_train_step(cfg, opt, remat=True,
                                   vocab_parallel=overrides.get("vocab_parallel", False))
            lowered = jax.jit(
                step,
                in_shardings=(p_shard, o_shard, b_shard),
                out_shardings=(p_shard, o_shard, None),
            ).lower(params_shape, opt_shape, specs["batch"])
        elif kind == "prefill":
            step = make_prefill_step(cfg, specs["max_seq"])
            cshape = cache_specs_shapes(cfg, gb, specs["max_seq"])
            c_shard = cache_specs(cfg, cshape, mesh)
            lowered = jax.jit(
                step,
                in_shardings=(p_shard, b_shard),
                out_shardings=(None, c_shard),
            ).lower(params_shape, specs["batch"])
        else:  # decode
            step = make_serve_step(cfg)
            c_shard = cache_specs(cfg, specs["caches"], mesh)
            donate = (1,) if overrides.get("donate_cache") else ()
            lowered = jax.jit(
                step,
                in_shardings=(p_shard, c_shard, b_shard),
                out_shardings=(None, c_shard),
                donate_argnums=donate,
            ).lower(params_shape, specs["caches"], specs["batch"])
        compiled = lowered.compile()
    return compiled


def _cost_triple(compiled):
    cost = compiled.cost_analysis()
    coll = parse_collectives(compiled.as_text())
    return (
        float(cost.get("flops", 0.0)),
        float(cost.get("bytes accessed", 0.0)),
        coll.total_link_bytes,
        coll,
    )


def lower_cell(arch: str, shape: str, *, multi_pod: bool, plan: str = "baseline",
               correct_scan: bool = True):
    cfg = configs.get_config(arch)
    if plan != "baseline":
        from repro.launch import plans

        cfg, overrides = plans.apply_plan(cfg, arch, shape, plan)
    else:
        overrides = {}
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = len(mesh.devices.reshape(-1))
    seq, gb, kind = configs.SHAPES[shape]

    t0 = time.time()
    compiled = _lower_one(cfg, shape, mesh, overrides)
    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    f_full, b_full, l_full, coll = _cost_triple(compiled)

    # Scan-body correction: XLA's cost_analysis counts a while-loop body
    # ONCE. A 2-group UNROLLED twin minus the full scanned program isolates
    # one group body exactly; full + (G-1)*body is the true per-step cost.
    G = cfg.n_groups
    flops, hbm, link = f_full, b_full, l_full
    corrected = False
    if correct_scan and G > 1:
        try:
            twin_cfg = dataclasses.replace(
                cfg,
                n_layers=2 * len(cfg.group),
                n_enc_layers=min(2, cfg.n_enc_layers),
            )
            twin = _lower_one(twin_cfg, shape, mesh, overrides, unroll_scan=True)
            f2, b2, l2, _ = _cost_triple(twin)
            scale = G - 1
            flops = f_full + scale * max(f2 - f_full, 0.0)
            hbm = b_full + scale * max(b2 - b_full, 0.0)
            link = l_full + scale * max(l2 - l_full, 0.0)
            corrected = True
        except Exception as e:  # keep raw HLO numbers
            print(f"     (scan correction failed: {e})")

    terms = roofline_terms(flops, hbm, link)
    mflops = model_flops(cfg, kind, seq, gb, chips=chips)
    rec = dict(
        arch=arch,
        shape=shape,
        mesh="2x16x16" if multi_pod else "16x16",
        plan=plan,
        chips=chips,
        kind=kind,
        compile_s=round(compile_s, 2),
        flops_per_device=flops,
        hbm_bytes_per_device=hbm,
        link_bytes_per_device=link,
        raw_flops_uncorrected=f_full,
        scan_corrected=corrected,
        collectives={k: v for k, v in coll.per_op.items()},
        model_flops_per_device=mflops,
        useful_flops_frac=(mflops / flops) if flops else None,
        arg_bytes=mem.argument_size_in_bytes,
        temp_bytes=mem.temp_size_in_bytes,
        output_bytes=mem.output_size_in_bytes,
        per_device_hbm_total=(
            mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes
        ),
        **terms,
    )
    return rec, mem, compiled.cost_analysis(), None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"], default="pod")
    ap.add_argument("--plan", default="baseline")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--no-correct", action="store_true",
                    help="skip the scan-body cost correction")
    ap.add_argument("--graph-engine", action="store_true",
                    help="also dry-run the subgraph-centric BSP engine (paper core)")
    args = ap.parse_args()

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)

    archs = configs.ARCHS if (args.all or args.arch is None) else [args.arch]
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]

    for arch in archs:
        shapes = configs.runnable_shapes(arch)
        if args.shape:
            if args.shape not in shapes:
                print(f"[skip] {arch} × {args.shape}: not runnable (DESIGN.md §4)")
                continue
            shapes = [args.shape]
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}__{shape}__{'mp' if mp else 'sp'}__{args.plan}"
                try:
                    rec, mem, cost, _ = lower_cell(
                        arch, shape, multi_pod=mp, plan=args.plan,
                        correct_scan=not args.no_correct,
                    )
                    print(f"[ok] {tag}: compile={rec['compile_s']}s "
                          f"bottleneck={rec['bottleneck']} bound={rec['bound_s']:.4f}s "
                          f"hbm/dev={rec['per_device_hbm_total']/2**30:.2f}GiB "
                          f"useful={rec['useful_flops_frac']:.3f}")
                    print(f"     memory_analysis: {mem}")
                    (outdir / f"{tag}.json").write_text(json.dumps(rec, indent=1))
                except Exception as e:
                    print(f"[FAIL] {tag}: {e}")
                    traceback.print_exc()
                    (outdir / f"{tag}.FAIL").write_text(str(e))

    if args.graph_engine:
        from repro.launch.graph_dryrun import run_graph_dryrun

        for mp in meshes:
            rec = run_graph_dryrun(multi_pod=mp)
            tag = f"graph_bsp__cc__{'mp' if mp else 'sp'}"
            (outdir / f"{tag}.json").write_text(json.dumps(rec, indent=1))
            print(f"[ok] {tag}: {rec['bottleneck']} bound={rec['bound_s']:.6f}s")


if __name__ == "__main__":
    main()
