"""Production training launcher.

Wires together: config registry, mesh + GSPMD sharding, resumable data
pipeline, AdamW + schedule, optional PowerSGD compression, async atomic
checkpointing, ABFT verification, straggler watchdog, preemption handling,
and elastic restore. This is the entry point a cluster scheduler re-execs
on every (re)start; all state recovery is automatic.

Step-fault rollback/retry: each step's ``step_ok`` metric (finite loss +
grad norm; an online-ABFT NaN-poison from ``--abft verify|correct`` trips
it too) gates a retry ladder -- roll back to the last in-memory host
snapshot and replay (bounded by ``--max-step-retries``), then escalate to
``Checkpointer.restore_latest_good``, then give up with a tagged error.
``--chaos-step N`` injects a one-shot NaN into the state before step N to
exercise exactly this path (see tests/test_train_rollback.py).

    python -m repro.launch.train --arch llama3.2-3b --steps 200 \
        --global-batch 8 --seq-len 128 --smoke --ckpt-dir /tmp/run1

On real TPU pods: run under `jax.distributed.initialize()` (flag
--distributed), one process per host; the mesh comes from launch/mesh.py
and XLA latency-hiding flags are set below. On one host the same code path
runs with the host mesh (``--devices N`` limits it to the first N devices;
--smoke uses reduced configs).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import os
import sys
import time

# Async-collective / latency-hiding flags for real TPU runs (no-ops on CPU).
_TPU_PERF_FLAGS = (
    "--xla_enable_async_all_gather=true "
    "--xla_enable_async_collective_permute=true "
    "--xla_tpu_enable_data_parallel_all_reduce_opt=true "
    "--xla_tpu_overlap_compute_collective_tc=true "
)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced per-arch config")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--abft-every", type=int, default=0,
                    help="verify param checksums every N steps (0=off)")
    ap.add_argument("--abft", choices=("none", "verify", "correct"),
                    default="none",
                    help="online per-GEMM checksum guard (GemmPolicy.abft)")
    ap.add_argument("--max-step-retries", type=int, default=2,
                    help="in-memory rollback replays per fault episode "
                         "before escalating to a checkpoint restore")
    ap.add_argument("--snapshot-every", type=int, default=1,
                    help="refresh the rollback host snapshot every N good "
                         "steps (0=never; faults then escalate directly)")
    ap.add_argument("--chaos-step", type=int, default=-1,
                    help="inject a one-shot NaN into the state before this "
                         "step (fault-injection drill; -1=off)")
    ap.add_argument("--powersgd-rank", type=int, default=0,
                    help="gradient compression rank (0=off)")
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--devices", type=int, default=0,
                    help="build the mesh over the first N devices "
                         "(0 = all of this host's)")
    ap.add_argument("--distributed", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    if args.distributed:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + " " + _TPU_PERF_FLAGS)
        import jax
        jax.distributed.initialize()
    import jax
    import jax.numpy as jnp

    from repro.launch.cache import configure_compilation_cache
    configure_compilation_cache()

    from repro.checkpoint.checkpointer import Checkpointer
    from repro.configs import registry
    from repro.core import tsmm
    from repro.data import pipeline
    from repro.distributed import sharding
    from repro.ft import abft, elastic, inject, watchdog
    from repro.launch.mesh import make_host_mesh
    from repro.models import moe
    from repro.optim import adamw, powersgd, schedule
    from repro.train import train_step as ts

    cfg = registry.get_config(args.arch, smoke=args.smoke)
    mesh = make_host_mesh(model=args.model_axis,
                          devices=(jax.devices()[:args.devices]
                                   if args.devices else None))
    if cfg.moe is not None and mesh.size > 1:
        # GSPMD cannot partition the dropless grouped matmul: capacity
        # dispatch, one group per data shard
        cfg = dataclasses.replace(cfg, moe=moe.for_gspmd(
            cfg.moe, mesh.shape["data"], args.global_batch * args.seq_len))
    host_index = jax.process_index()
    host_count = jax.process_count()

    dcfg = pipeline.DataConfig(
        seed=0, seq_len=args.seq_len, global_batch=args.global_batch,
        vocab_size=cfg.vocab_size, host_index=host_index,
        host_count=host_count,
        mode="frames" if cfg.input_mode == "frames" else "tokens",
        frame_dim=cfg.frame_dim, vision_seq=cfg.vision_seq,
        vision_dim=cfg.vision_dim)

    opt_cfg = adamw.AdamWConfig(
        lr=schedule.linear_warmup_cosine(args.lr, args.warmup, args.steps),
        weight_decay=0.1)

    # --- sharding-aware state init / restore -------------------------------
    key_s = jax.ShapeDtypeStruct((2,), jnp.uint32)
    params_shape = jax.eval_shape(lambda k: ts.init_train_state(
        k, cfg, opt_cfg)["params"], key_s)
    p_specs = sharding.make_param_specs(cfg, params_shape, mesh)
    p_named = sharding.named(mesh, p_specs)
    state_specs = {"params": p_specs, "opt": sharding.make_opt_specs(p_specs)}
    state_named = sharding.named(mesh, state_specs)

    grad_transform = None
    extra = None
    if args.powersgd_rank:
        ps_cfg = powersgd.PowerSGDConfig(rank=args.powersgd_rank)
        params_eval = jax.eval_shape(lambda k: ts.init_train_state(
            k, cfg, opt_cfg)["params"], key_s)
        extra = powersgd.init(ps_cfg, params_eval, jax.random.PRNGKey(17))
        extra = jax.tree.map(
            lambda s: (jax.numpy.zeros(s.shape, s.dtype)
                       if hasattr(s, "shape") else s), extra,
            is_leaf=lambda x: x is None or hasattr(x, "shape"))

        def grad_transform(grads, st):
            return powersgd.compress_tree(ps_cfg, grads, st)

    step_fn = jax.jit(
        ts.make_train_step(cfg, opt_cfg, n_micro=cfg.microbatch,
                           grad_transform=grad_transform,
                           acc_shardings=p_named),
        donate_argnums=(0,))

    ckpt = Checkpointer(args.ckpt_dir, keep_n=3) if args.ckpt_dir else None
    start_step = 0
    if ckpt and ckpt.latest_step() is not None:
        state, start_step = elastic.restore_state(
            ckpt, cfg, elastic.rescale_plan(model_axis=args.model_axis,
                                            host_index=host_index,
                                            host_count=host_count),
            {"params": params_shape})
        print(f"[train] restored checkpoint at step {start_step}")
        start_step += 1
    else:
        with jax.set_mesh(mesh):
            state = jax.jit(
                lambda k: ts.init_train_state(k, cfg, opt_cfg, extra=extra),
                out_shardings=(state_named if extra is None else None),
            )(jax.random.PRNGKey(0))

    wd = watchdog.StepWatchdog(
        on_straggler=lambda dt, ewma: print(
            f"[watchdog] straggler step: {dt:.2f}s vs ewma {ewma:.2f}s "
            "-- scheduling proactive checkpoint"))
    preempt = watchdog.PreemptionHandler()
    prefetch = pipeline.Prefetcher(dcfg, start_step=start_step)

    def refetch(from_step):
        nonlocal prefetch
        prefetch.close()
        prefetch = pipeline.Prefetcher(dcfg, start_step=from_step)

    # Rollback ladder state: last-known-good in-memory snapshot, bounded
    # replays per fault episode, then checkpoint escalation.
    snap = None                       # (step, host pytree)
    retries_left = args.max_step_retries
    total_retries = 0
    chaos_pending = args.chaos_step >= 0
    last_metrics = {}
    losses = []                       # loss of every good step, in order

    abft_scope = (tsmm.policy(abft=args.abft) if args.abft != "none"
                  else contextlib.nullcontext())
    t_start = time.time()
    try:
        with abft_scope:
            cur = start_step
            while cur < args.steps:
                step, host_batch = prefetch.get()
                batch = jax.tree.map(jnp.asarray, host_batch)
                if chaos_pending and step == args.chaos_step:
                    # One-shot drill: a transient in-memory fault the
                    # step_ok gate must catch and the ladder must undo.
                    # Target the params subtree specifically -- the fault
                    # must surface in THIS step's loss, not launder
                    # through the optimizer state into a state the gate
                    # passes (and the snapshot would then preserve).
                    state = {**state,
                             "params": inject.poison_tree(state["params"])}
                    chaos_pending = False
                    print(f"[chaos] poisoned state before step {step}")
                with wd:
                    with jax.set_mesh(mesh):
                        state, metrics = step_fn(state, batch)
                    step_ok = bool(metrics["step_ok"])
                if not step_ok:
                    wd.note_fault()
                    total_retries += 1
                    if retries_left > 0 and snap is not None:
                        retries_left -= 1
                        state = ts.restore_snapshot(snap[1])
                        cur = snap[0] + 1
                        refetch(cur)
                        print(f"[ft] step {step} fault: rolled back to "
                              f"snapshot at step {snap[0]}, replaying "
                              f"({retries_left} retries left)", flush=True)
                        continue
                    if ckpt and ckpt.all_steps():
                        state, rstep = ckpt.restore_latest_good()
                        state = jax.tree.map(jnp.asarray, state)
                        cur = rstep + 1
                        refetch(cur)
                        snap = None
                        retries_left = args.max_step_retries
                        print(f"[ft] step {step} fault: retries exhausted, "
                              f"restored checkpoint step {rstep}", flush=True)
                        continue
                    raise RuntimeError(
                        f"[ft-retries] step {step} faulted with no snapshot "
                        "retries left and no restorable checkpoint")
                # -- good step ------------------------------------------
                retries_left = args.max_step_retries
                last_metrics = metrics
                losses.append(float(metrics["loss"]))
                wm = wd.last_metrics
                if args.snapshot_every and step % args.snapshot_every == 0:
                    snap = (step, ts.host_snapshot(state))
                if step % args.log_every == 0 or step == args.steps - 1:
                    print(f"[train] step {step} "
                          f"loss {float(metrics['loss']):.4f} "
                          f"acc {float(metrics['accuracy']):.3f} "
                          f"gnorm {float(metrics['grad_norm']):.2f} "
                          f"{wm['step_time_s']:.2f}s", flush=True)
                if ckpt and (step % args.ckpt_every == 0
                             or step == args.steps - 1 or preempt.requested):
                    if args.abft_every and step % args.abft_every == 0:
                        # encode -> verify -> save: the verify re-encodes,
                        # catching SDC landing on the params between the
                        # two passes, BEFORE the state is persisted -- a
                        # detected-corrupt tree must never become the
                        # newest checkpoint.
                        checksums = abft.encode_tree(state["params"])
                        ok, _ = abft.verify_tree(state["params"], checksums)
                        if not bool(ok):
                            raise RuntimeError(
                                "[abft] silent data corruption detected in "
                                "params -- refusing to persist; restore + "
                                "replay")
                    ckpt.save(step, state)
                if preempt.requested:
                    print("[train] preemption requested: checkpointed, "
                          "exiting 42")
                    ckpt and ckpt.wait()
                    sys.exit(42)   # scheduler contract: re-exec to resume
                cur = step + 1
    finally:
        prefetch.close()
        preempt.restore()
        if ckpt:
            ckpt.wait()
    dt = time.time() - t_start
    steps_run = args.steps - start_step
    print(f"[train] done: {steps_run} steps in {dt:.1f}s "
          f"({steps_run / max(dt, 1e-9):.2f} steps/s); "
          f"fault retries: {total_retries}")
    return {"final_loss": float(last_metrics.get("loss", float("nan"))),
            "losses": losses,
            "final_step": args.steps - 1,
            "fault_retries": total_retries,
            "fault_events": wd.fault_events}


if __name__ == "__main__":
    main()
