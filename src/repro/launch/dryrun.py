import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry run: AOT lower + compile every (arch x shape x mesh) cell.

The lines above MUST stay first: jax locks the device count at first
initialization, and the production meshes need 512 placeholder host
devices. The dry run is a CPU tool: ``JAX_PLATFORMS=cpu`` keeps this
process and the per-cell children of ``--all`` (which inherit the
environment) off any accelerator, so no child waits on a chip the parent
holds. Smoke tests / benchmarks never import this module and keep 1
device.

Per cell this produces (artifacts/dryrun/<arch>__<shape>__<mesh>.json):
  * proof of compile (the deliverable: sharding is coherent),
  * memory_analysis()  -- per-device bytes (argument/temp/output),
  * cost_analysis()    -- HLO FLOPs / bytes (per partition),
  * parsed collective wire bytes (roofline/analyze.py),
  * the three roofline terms + dominant bottleneck + 6ND ratio.

Run one cell:   python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
Run everything: python -m repro.launch.dryrun --all   (subprocess per cell,
                smallest archs first, already-done cells skipped)
"""

import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                         "artifacts", "dryrun")


def _cell_path(arch, shape, mesh_kind, out_dir, strategy="tp", variant=None):
    suffix = ("" if strategy == "tp" else f"__{strategy}") + \
        ("" if not variant else f"__{variant}")
    return os.path.join(out_dir, f"{arch}__{shape}__{mesh_kind}{suffix}.json")


def input_specs(cfg, shape, kind: str):
    """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
    import jax
    import jax.numpy as jnp
    b, s = shape.global_batch, shape.seq_len
    if kind == "train":
        batch = {}
        if cfg.input_mode == "frames":
            batch["frames"] = jax.ShapeDtypeStruct((b, s, cfg.frame_dim), jnp.bfloat16)
        else:
            batch["tokens"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        batch["targets"] = jax.ShapeDtypeStruct((b, s), jnp.int32)
        if cfg.family == "vlm":
            batch["image_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.vision_seq, cfg.vision_dim), jnp.bfloat16)
        return batch
    if kind == "prefill":
        if cfg.input_mode == "frames":   # encoder: prefill = full forward
            return {"frames": jax.ShapeDtypeStruct((b, s, cfg.frame_dim),
                                                   jnp.bfloat16)}
        batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        if cfg.family == "vlm":
            batch["image_embeds"] = jax.ShapeDtypeStruct(
                (b, cfg.vision_seq, cfg.vision_dim), jnp.bfloat16)
        return batch
    # decode: one new token against a cache of seq_len
    return {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)}


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               strategy: str = "tp", variant: str | None = None):
    import jax
    import jax.numpy as jnp

    from repro.configs import registry
    from repro.configs.base import SHAPES
    from repro.distributed import sharding
    from repro.launch.mesh import make_production_mesh
    from repro.models import model, moe
    from repro.optim import adamw, schedule
    from repro.roofline import analyze
    from repro.train import train_step as ts

    cfg = registry.get_config(arch)
    if variant == "noabsorb":
        cfg = dataclasses.replace(cfg, mla_absorb=False)
    elif variant and variant.startswith("mb"):
        import re as _re
        cfg = dataclasses.replace(
            cfg, microbatch=int(_re.match(r"mb(\d+)", variant).group(1)))
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    if cfg.moe is not None:
        # GSPMD cannot partition the dropless grouped matmul: capacity
        # dispatch, one group per DP shard
        cfg = dataclasses.replace(cfg, moe=moe.for_gspmd(
            cfg.moe, n_chips // mesh.shape["model"],
            shape.global_batch * shape.seq_len))
    t0 = time.time()

    key_s = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params_shape = jax.eval_shape(lambda k: model.init(k, cfg), key_s)
    p_specs = sharding.make_param_specs(cfg, params_shape, mesh,
                                        strategy=strategy)
    p_named = sharding.named(mesh, p_specs)

    if shape.kind == "train":
        opt_cfg = adamw.AdamWConfig(
            lr=schedule.linear_warmup_cosine(3e-4, 2000, 100000),
            state_dtype="bfloat16" if cfg.param_count() > 1e11 else None)
        state_shape = jax.eval_shape(
            lambda k: ts.init_train_state(k, cfg, opt_cfg), key_s)
        state_specs = {"params": p_specs,
                       "opt": sharding.make_opt_specs(
                           p_specs, mesh=mesh, params_shape=params_shape,
                           zero1=(strategy == "dp"))}
        state_named = sharding.named(mesh, state_specs)
        batch_shape = input_specs(cfg, shape, "train")
        b_named = sharding.named(
            mesh, sharding.batch_specs(cfg, mesh, batch_shape, strategy))
        n_micro = 0 if strategy == "dp" else cfg.microbatch
        upd_specs = (jax.tree.map(lambda mv: mv["m"],
                                  state_specs["opt"]["moments"],
                                  is_leaf=lambda x: isinstance(x, dict)
                                  and "m" in x)
                     if strategy == "dp" else None)
        step_fn = ts.make_train_step(cfg, opt_cfg, n_micro=n_micro,
                                     acc_shardings=p_named, mesh=mesh,
                                     opt_update_specs=upd_specs)
        with jax.set_mesh(mesh):
            # donate the train state: params/opt buffers alias in-place
            lowered = jax.jit(step_fn,
                              in_shardings=(state_named, b_named),
                              out_shardings=(state_named, None),
                              donate_argnums=(0,)
                              ).lower(state_shape, batch_shape)
    elif shape.kind == "prefill":
        batch_shape = input_specs(cfg, shape, "prefill")
        b_named = sharding.named(
            mesh, sharding.batch_specs(cfg, mesh, batch_shape))
        if cfg.input_mode == "frames":
            # encoder-only: "prefill" = the batched encoder forward pass
            def encode_step(params, batch):
                return model.forward(params, cfg, batch)

            with jax.set_mesh(mesh):
                lowered = jax.jit(encode_step,
                                  in_shardings=(p_named, b_named)
                                  ).lower(params_shape, batch_shape)
        else:
            cache_shape = jax.eval_shape(
                lambda: model.init_cache(cfg, shape.global_batch, shape.seq_len))
            c_named = sharding.named(
                mesh, sharding.cache_specs(cfg, mesh, cache_shape))

            def prefill_step(params, batch, cache):
                return model.prefill(params, cfg, batch, cache)

            with jax.set_mesh(mesh):
                lowered = jax.jit(prefill_step,
                                  in_shardings=(p_named, b_named, c_named),
                                  out_shardings=(None, c_named)
                                  ).lower(params_shape, batch_shape, cache_shape)
    else:  # decode
        cache_shape = jax.eval_shape(
            lambda: model.init_cache(cfg, shape.global_batch, shape.seq_len))
        c_named = sharding.named(
            mesh, sharding.cache_specs(cfg, mesh, cache_shape))
        tok_shape = input_specs(cfg, shape, "decode")["tokens"]
        t_named = sharding.named(
            mesh, sharding.batch_specs(cfg, mesh, {"tokens": tok_shape}))["tokens"]

        def decode_step(params, tokens, pos, cache):
            return model.decode_step(params, cfg, tokens, pos, cache)

        with jax.set_mesh(mesh):
            lowered = jax.jit(decode_step,
                              in_shardings=(p_named, t_named, None, c_named),
                              out_shardings=(None, c_named)
                              ).lower(params_shape, tok_shape,
                                      jax.ShapeDtypeStruct((), jnp.int32),
                                      cache_shape)

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower

    xla_cost = analyze.xla_cost_dict(compiled)
    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
        }
        mem["total_bytes"] = (mem["argument_bytes"] + mem["output_bytes"]
                              + mem["temp_bytes"] - mem["alias_bytes"])
        mem["fits_16gb_hbm"] = bool(mem["total_bytes"] <= analyze.V5E["hbm_per_chip"])
    except Exception as e:  # backend without memory analysis
        mem = {"error": repr(e)}

    hlo = compiled.as_text()
    report = analyze_hlo(hlo, cfg, shape, n_chips, xla_cost=xla_cost)
    report.update({
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": n_chips, "kind": shape.kind,
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
        "strategy": strategy, "variant": variant,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": mem,
        "status": "ok",
    })
    return report, hlo


def analyze_hlo(hlo: str, cfg, shape, n_chips: int, xla_cost=None):
    """Roofline terms from optimized HLO (loop-aware; re-runnable offline)."""
    from repro.roofline import analyze

    cost = analyze.hlo_cost(hlo)
    coll = analyze.parse_collectives(hlo)
    terms = analyze.roofline_terms(cost, coll, n_chips)
    mf = analyze.model_flops(cfg, shape)
    terms["model_flops_total"] = mf
    terms["model_flops_per_chip"] = mf / n_chips
    terms["useful_flops_ratio"] = (mf / n_chips) / max(terms["hlo_flops"], 1.0)
    return {
        "cost_flops": terms["hlo_flops"],
        "cost_bytes": terms["hlo_bytes"],
        "xla_cost_flops_unrolled_once": float((xla_cost or {}).get("flops", 0)),
        "roofline": {k: terms[k] for k in
                     ("compute_s", "memory_s", "collective_s", "dominant",
                      "collective_bytes", "useful_flops_ratio")},
        "collective_counts": terms["collective_counts"],
        "collective_by_kind": terms["collective_by_kind"],
    }


def run_cell(arch, shape_name, mesh_kind, out_dir, strategy="tp", variant=None):
    path = _cell_path(arch, shape_name, mesh_kind, out_dir, strategy, variant)
    os.makedirs(out_dir, exist_ok=True)
    try:
        report, hlo = build_cell(arch, shape_name, mesh_kind == "multi",
                                 strategy, variant)
        import gzip
        with gzip.open(path[:-5] + ".hlo.gz", "wt") as f:
            f.write(hlo)
        print(f"[dryrun] {arch} x {shape_name} x {mesh_kind}: OK "
              f"(compile {report['compile_s']}s, dominant="
              f"{report['roofline']['dominant']})")
        if isinstance(report["memory"], dict) and "total_bytes" in report["memory"]:
            print(f"  memory/device: {report['memory']['total_bytes']/2**30:.2f} GiB "
                  f"(fits 16GiB: {report['memory']['fits_16gb_hbm']})")
        print(f"  flops/chip: {report['cost_flops']:.3e}  bytes/chip: "
              f"{report['cost_bytes']:.3e}  collective bytes/chip: "
              f"{report['roofline']['collective_bytes']:.3e}")
    except Exception:
        report = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                  "status": "error", "traceback": traceback.format_exc()}
        print(f"[dryrun] {arch} x {shape_name} x {mesh_kind}: FAILED",
              file=sys.stderr)
        print(report["traceback"], file=sys.stderr)
    with open(path, "w") as f:
        json.dump(report, f, indent=2, default=str)
    return report.get("status") == "ok"


# Smallest-compile-first ordering for --all.
_ARCH_ORDER = [
    "rwkv6-1.6b", "zamba2-1.2b", "hubert-xlarge", "chatglm3-6b",
    "llama3.2-3b", "mistral-nemo-12b", "llama-3.2-vision-11b",
    "mixtral-8x7b", "qwen2-72b", "deepseek-v3-671b",
]


def reanalyze(out_dir):
    """Recompute roofline JSONs from saved .hlo.gz (no recompilation)."""
    import glob
    import gzip

    from repro.configs import registry
    from repro.configs.base import SHAPES

    for hf in sorted(glob.glob(os.path.join(out_dir, "*.hlo.gz"))):
        jf = hf[:-7] + ".json"
        if not os.path.exists(jf):
            continue
        with open(jf) as f:
            report = json.load(f)
        if report.get("status") != "ok":
            continue
        cfg = registry.get_config(report["arch"])
        shape = SHAPES[report["shape"]]
        with gzip.open(hf, "rt") as f:
            hlo = f.read()
        report.update(analyze_hlo(hlo, cfg, shape, report["n_chips"]))
        with open(jf, "w") as f:
            json.dump(report, f, indent=2, default=str)
        print(f"[reanalyze] {os.path.basename(jf)}: "
              f"dominant={report['roofline']['dominant']} "
              f"6ND/HLO={report['roofline']['useful_flops_ratio']:.2f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--reanalyze", action="store_true")
    ap.add_argument("--strategy", default="tp", choices=["tp", "dp"])
    ap.add_argument("--variant", default=None)
    ap.add_argument("--out", default=os.path.abspath(ARTIFACTS))
    args = ap.parse_args()

    if args.reanalyze:
        reanalyze(args.out)
        return

    from repro.configs import registry

    if args.all:
        cells = []
        for arch in _ARCH_ORDER:
            for shape in ("decode_32k", "long_500k", "train_4k", "prefill_32k"):
                ok, _ = registry.cell_supported(arch, shape)
                if not ok:
                    continue
                for mesh_kind in (("single", "multi") if args.mesh == "both"
                                  else (args.mesh,)):
                    cells.append((arch, shape, mesh_kind))
        todo = [c for c in cells if args.force or
                not os.path.exists(_cell_path(*c, args.out))]
        print(f"[dryrun] {len(todo)}/{len(cells)} cells to run")
        failures = 0
        for arch, shape, mesh_kind in todo:
            r = subprocess.run(
                [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
                 "--shape", shape, "--mesh", mesh_kind, "--out", args.out],
                env={**os.environ, "PYTHONPATH": os.environ.get("PYTHONPATH", "src")})
            failures += r.returncode != 0
        sys.exit(1 if failures else 0)

    ok = run_cell(args.arch, args.shape, args.mesh, args.out,
                  args.strategy, args.variant)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
