"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

``main()`` asks for 512 placeholder host devices (``XLA_FLAGS``) before
any device query: jax locks the device count on first init, and the
production meshes need 512. Importing this module changes nothing, so
``run_cell`` serves callers that bring their own (small) mesh.

For each cell this driver:
  1. builds ShapeDtypeStruct stand-ins (configs/shapes.py -- no allocation),
  2. jits the step with in/out shardings from launch/sharding.py,
  3. ``.lower()`` + ``.compile()`` under the mesh,
  4. records memory_analysis / cost_analysis / loop-weighted collective
     bytes (launch/hlo_analysis.py) into a JSON artifact.

Usage:
  python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all --mesh both --out experiments/dryrun
Failures (sharding mismatch, OOM-at-compile, unsupported collective) are
bugs; the harness records them rather than crashing the sweep.
"""

import argparse
import dataclasses
import json
import os
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def _steps_module():
    from repro.launch import steps
    return steps


def run_cell(cfg, case, mesh, *, opts=None, fsdp=None, extra=None):
    """Lower+compile one (arch, shape, mesh) cell; return the record dict."""
    from repro.configs.shapes import applicable, batch_specs, cache_specs, param_specs
    from repro.launch import sharding as sh
    from repro.launch.hlo_analysis import (collective_bytes,
                                           cost_analysis_dict,
                                           loop_weighted_flops)
    from repro.launch.steps import (StepOptions, make_prefill_step,
                                    make_serve_step, make_train_step,
                                    train_state_specs)

    skip = applicable(cfg, case)
    rec = {
        "arch": cfg.name, "shape": case.name, "kind": case.kind,
        "mesh": {"shape": tuple(int(mesh.shape[a]) for a in mesh.axis_names),
                 "axes": tuple(mesh.axis_names)},
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "config_overrides": extra or {},
    }
    if skip:
        rec["status"] = "skipped"
        rec["reason"] = skip
        return rec

    opts = opts or StepOptions()
    pol = sh.ShardingPolicy.for_arch(cfg, mesh, fsdp=fsdp)
    rec["fsdp"] = pol.fsdp
    # anchor activation batch sharding when the (micro)batch divides
    dsize = 1
    for a in pol.data:
        dsize *= mesh.shape[a]
    eff_batch = case.global_batch // max(opts.microbatch, 1)
    batch_divides = eff_batch % dsize == 0
    updates = {"ep_axis": pol.model} if cfg.moe else {}
    if batch_divides:
        updates["act_sharding"] = tuple(pol.data)
    # auto q-chunk: cap the per-device f32 score matrix near 2 GiB
    if case.kind in ("train", "prefill") and cfg.q_chunk is None:
        per_dev_b = max(eff_batch // (dsize if batch_divides else 1), 1)
        msize = sh._axis_size(mesh, pol.model)
        h_dev = cfg.num_heads // msize if cfg.num_heads % msize == 0 \
            else cfg.num_heads
        score_bytes = per_dev_b * h_dev * case.seq_len ** 2 * 4
        cap = 2 << 30
        if score_bytes > cap:
            import math
            div = 1 << math.ceil(math.log2(score_bytes / cap))
            qc = max(256, case.seq_len // div)
            updates["q_chunk"] = int(qc)
    if updates:
        cfg = dataclasses.replace(cfg, **updates)
        rec["auto_overrides"] = {
            k: (list(v) if isinstance(v, tuple) else v)
            for k, v in updates.items()}
    t0 = time.time()
    try:
        with mesh:
            if case.kind == "train":
                state_sds, state_sh = train_state_specs(
                    cfg, mesh, pol, compress=opts.compress_grads)
                bsds = batch_specs(cfg, case, dtype=cfg.cdtype)
                bsh = sh.batch_shardings(cfg, mesh, pol, bsds)
                metrics_sh = None  # replicated scalars; let jit default
                fn = make_train_step(cfg, opts,
                                     grad_shardings=state_sh["params"])
                jitted = jax.jit(fn, in_shardings=(state_sh, bsh),
                                 out_shardings=(state_sh, metrics_sh),
                                 donate_argnums=(0,))
                lowered = jitted.lower(state_sds, bsds)
            elif case.kind == "prefill":
                psds = param_specs(cfg)
                psh = sh.params_shardings(cfg, mesh, pol, psds)
                bsds = batch_specs(cfg, case, dtype=cfg.cdtype)
                bsh = sh.batch_shardings(cfg, mesh, pol, bsds)
                csds = cache_specs(cfg, case)
                csh = sh.cache_shardings(cfg, mesh, pol, csds)
                b_ax = tuple(pol.data) if batch_divides else None
                logits_sh = NamedSharding(mesh, P(b_ax, None))
                fn = make_prefill_step(cfg)
                jitted = jax.jit(fn, in_shardings=(psh, bsh),
                                 out_shardings=(logits_sh, csh))
                lowered = jitted.lower(psds, bsds)
            else:  # decode
                psds = param_specs(cfg)
                psh = sh.params_shardings(cfg, mesh, pol, psds)
                csds = cache_specs(cfg, case)
                csh = sh.cache_shardings(cfg, mesh, pol, csds)
                bsds = batch_specs(cfg, case, dtype=cfg.cdtype)
                bsh = sh.batch_shardings(cfg, mesh, pol, bsds)
                b_ax = tuple(pol.data) if batch_divides else None
                tok_sh = NamedSharding(mesh, P(b_ax, None))
                fn = make_serve_step(cfg)
                jitted = jax.jit(fn, in_shardings=(psh, csh, bsh),
                                 out_shardings=(tok_sh, csh),
                                 donate_argnums=(1,))
                lowered = jitted.lower(psds, csds, bsds)

            compiled = lowered.compile()
        rec["lower_compile_s"] = round(time.time() - t0, 2)

        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "peak_per_device_bytes": int(
                ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes),
        }
        ca = cost_analysis_dict(compiled)
        rec["cost"] = {"flops": float(ca.get("flops", 0.0)),
                       "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
        hlo = compiled.as_text()
        rec["collectives"] = collective_bytes(hlo).as_dict()
        rec["loops"] = loop_weighted_flops(hlo, rec["cost"]["flops"])
        rec["hlo_ops"] = {
            k: hlo.count(k + "(") for k in
            ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
             "collective-permute", "dynamic-slice", "dynamic-update-slice")}
        rec["status"] = "ok"
    except Exception as e:  # record, don't crash the sweep
        rec["status"] = "failed"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc(limit=6)
    return rec


def apply_overrides(cfg, overrides):
    if not overrides:
        return cfg
    return dataclasses.replace(cfg, **overrides)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="single")
    ap.add_argument("--model-split", type=int, default=None,
                    help="factor the model axis: (model_a=s, model_b=16/s) "
                         "2-D TP for head-misaligned archs (whisper)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--microbatch", type=int, default=0,
                    help="grad-accum chunks; 0 = auto (fit remat carries)")
    ap.add_argument("--fsdp", choices=("auto", "on", "off"), default="auto")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--q-chunk", type=int, default=None)
    ap.add_argument("--remat", choices=("on", "off"), default="on")
    ap.add_argument("--remat-policy", choices=("full", "dots"), default=None)
    ap.add_argument("--kv-dtype", choices=("bfloat16", "int8"), default=None)
    ap.add_argument("--moe-group", type=int, default=None)
    ap.add_argument("--moe-cf", type=float, default=None)
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args(argv)
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

    from repro.configs import registry
    from repro.configs.shapes import SHAPES
    from repro.launch.mesh import make_production_mesh
    from repro.launch.steps import StepOptions

    regs = registry()
    archs = list(regs) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    fsdp = {"auto": None, "on": True, "off": False}[args.fsdp]

    outdir = Path(args.out)
    outdir.mkdir(parents=True, exist_ok=True)
    n_ok = n_fail = n_skip = 0
    for multi in meshes:
        mesh = make_production_mesh(multi_pod=multi,
                                    model_split=args.model_split)
        mesh_name = "multi" if multi else "single"
        if args.model_split:
            mesh_name += f"-split{args.model_split}"
        for arch in archs:
            cfg = regs[arch] if arch in regs else None
            if cfg is None:
                from repro.configs import get_config
                cfg = get_config(arch)
            overrides = {}
            extra_rec = {}  # JSON-able record of what was overridden
            if args.q_chunk:
                overrides["q_chunk"] = extra_rec["q_chunk"] = args.q_chunk
            if args.remat == "off":
                overrides["remat"] = extra_rec["remat"] = False
            if args.remat_policy:
                overrides["remat_policy"] = args.remat_policy
                extra_rec["remat_policy"] = args.remat_policy
            if args.kv_dtype:
                overrides["kv_cache_dtype"] = args.kv_dtype
                extra_rec["kv_cache_dtype"] = args.kv_dtype
            if (args.moe_group or args.moe_cf) and cfg.moe:
                overrides["moe"] = dataclasses.replace(
                    cfg.moe,
                    group_size=args.moe_group or cfg.moe.group_size,
                    capacity_factor=args.moe_cf or cfg.moe.capacity_factor)
                extra_rec["moe_group"] = overrides["moe"].group_size
                extra_rec["moe_cf"] = overrides["moe"].capacity_factor
            cfg_run = apply_overrides(cfg, overrides)
            for shape in shapes:
                fname = outdir / f"{args.tag}--{cfg.name}--{shape}--{mesh_name}.json"
                if args.skip_existing and fname.exists():
                    print(f"[skip-existing] {fname.name}")
                    continue
                case = SHAPES[shape]
                from repro.launch.mesh import data_axes
                from repro.launch.steps import auto_microbatch
                mb = args.microbatch or auto_microbatch(cfg_run, case, mesh)
                opts = StepOptions(microbatch=mb,
                                   compress_grads=args.compress_grads,
                                   data_axes=data_axes(mesh))
                rec = run_cell(cfg_run, case, mesh, opts=opts, fsdp=fsdp,
                               extra={**extra_rec, "microbatch": mb})
                rec["mesh_name"] = mesh_name
                rec["tag"] = args.tag
                fname.write_text(json.dumps(rec, indent=1))
                st = rec["status"]
                n_ok += st == "ok"
                n_fail += st == "failed"
                n_skip += st == "skipped"
                msg = rec.get("error", rec.get("reason", ""))
                if st == "ok":
                    mem = rec["memory"]["peak_per_device_bytes"] / 2**30
                    msg = (f"peak/dev={mem:.2f}GiB flops={rec['cost']['flops']:.3g} "
                           f"coll={rec['collectives']['total_bytes']:.3g}B "
                           f"t={rec['lower_compile_s']}s")
                print(f"[{st:7s}] {cfg.name:24s} {shape:12s} {mesh_name:6s} {msg}",
                      flush=True)
    print(f"done: ok={n_ok} failed={n_fail} skipped={n_skip}")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
