#!/usr/bin/env python3
"""Chip smoke test: drive the TSM2X main path once on a TPU and check it.

    python chip_smoke.py              # one chip: kernel phase + model phase
    python chip_smoke.py --chips 4    # four chips: the data-parallel path only

Kernel phase (one chip). ``tsmm`` in bf16 at the paper's tall-and-skinny
shapes (``benchmarks/run.py`` ``CANONICAL_SHAPES`` minus the dense control)
plus the rwkv6 decay-LoRA shape, and ``tsmm_t`` at the PowerSGD shape
``(2^20, 16)^T (2^20, 16)``. Each call must dispatch its TSM2X kind on the
``pallas-tpu`` executor, compile to a Mosaic kernel (``tpu_custom_call``)
built without interpret mode, and match the same call under
``tsmm.policy(mode="dense")`` on the chip within ``KERNEL_TOL``.

Model phase (one chip). rwkv6-1.6b at its published widths, bf16 params
from ``model.init(PRNGKey(seed))``, served through ``repro.serve.engine``:
batch 4 x prompt 1024 (4096 tokens, so the decay LoRA classifies as
``tsm2r``), then 16 decode steps. Prefill must dispatch ``tsm2r`` on
``pallas-tpu``; its last-token logits must match the ``mode="dense"`` arm
within ``PREFILL_TOL``; every decode step's logits must match the
teacher-forced ``model.forward`` at the same position within
``DECODE_TOL``. Logits are compared, not argmax tokens: bf16 near-ties
flip.

Four chips (``--chips 4``). ``tsmm_t`` at the PowerSGD shape under a
4-chip ``("data",)`` mesh with ``reduce="psum"`` and ``"psum_scatter"``
(executor and per-shard ``pallas-tpu`` events asserted, result compared
with the one-device call), and a few ``launch/train.py`` steps with
``--powersgd-rank`` on the 4-chip data mesh, compared step by step with the
same global batch on one device.

Every error is relative to the reference's largest magnitude:
``max|out - ref| / max|ref|``. The times and memory printed on the way
(compile seconds, kernel and prefill milliseconds, decode tokens/s,
``peak_bytes_in_use``) are smoke readings from one run, not benchmark
numbers.

Exits non-zero, with no result line, when JAX finds no TPU, when the repo's
``src/`` is not next to this file, or when any check fails. On success the
last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(HERE, "src")

# bf16 outputs round at 2^-8 relative; kernel and XLA dot also sum in a
# different order. Both arms accumulate in f32.
KERNEL_TOL = 1e-2
# Logits of a random-weight 24-layer bf16 model amplify one-ulp bf16
# differences in any activation to a few percent of the largest logit: on a
# narrowed copy of this model (d_model 512, all 24 layers) on the CPU,
# decode vs forward differed by 3.4-5.1% and a one-ulp perturbation of 20%
# of the LoRA outputs moved the prefill logits by 6.7%; both are 0 up to
# 5e-5 in f32. A wrong kernel, position or state moves them by order 100%.
# The two prefill arms differ only in how the LoRA's tsm2r product rounds.
PREFILL_TOL = 0.15
# Decode runs the per-token recurrence, the forward the chunked scan.
DECODE_TOL = 0.15
# Four-chip tsmm_t: each of the 4 per-shard partials is rounded to bf16
# (unit roundoff 2^-8) before the bf16 collective sums them, and the
# one-device reference is rounded once more.
SHARDED_TOL = 2e-2
# PowerSGD training steps (smoke config, f32): loss per step, relative.
TRAIN_TOL = 1e-3

MODEL_ARCH = "rwkv6-1.6b"
BATCH, PROMPT, DECODE_STEPS = 4, 1024, 16
POWERSGD_SHAPE = (1 << 20, 16, 16)          # (m, a, b) for X^T Y
LORA_SHAPE = (8192, 2048, 64)               # rwkv6 decay LoRA, B*S = 8192


def _say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def rel_err(out, ref) -> float:
    """max|out - ref| / max|ref|."""
    import numpy as np
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    scale = float(np.max(np.abs(ref)))
    return float(np.max(np.abs(out - ref))) / max(scale, 1e-30)


def rms_err(out, ref) -> float:
    """rms(out - ref) / rms(ref): printed beside :func:`rel_err`."""
    import numpy as np
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.sqrt(np.mean((out - ref) ** 2))
                 / max(np.sqrt(np.mean(ref ** 2)), 1e-30))


def kernel_cases():
    """(name, entry, kind, lhs shape, rhs shape) of the kernel phase."""
    from benchmarks.run import CANONICAL_SHAPES
    cases = []
    for m, k, n in CANONICAL_SHAPES:
        if (m, k, n) == (4096, 4096, 1024):     # the dense control
            continue
        kind = "tsm2l" if k <= 256 else "tsm2r"
        cases.append((f"tsmm {m}x{k}x{n}", "mm", kind, (m, k), (k, n)))
    m, k, n = LORA_SHAPE
    cases.append((f"tsmm lora {m}x{k}x{n}", "mm", "tsm2r", (m, k), (k, n)))
    m, a, b = POWERSGD_SHAPE
    cases.append((f"tsmm_t powersgd {m}x{a}x{b}", "mmt", "tsmt",
                  (m, a), (m, b)))
    return cases


def kernel_fn(entry: str, dense: bool):
    """A fresh jitted callable per arm (jit caches key on the callable)."""
    import jax
    from repro.core import tsmm
    mode = "dense" if dense else None
    if entry == "mm":
        return jax.jit(lambda a, b: tsmm.tsmm(a, b, mode=mode))
    return jax.jit(lambda x, y: tsmm.tsmm_t(x, y, mode=mode))


def _timed_ms(compiled, *args, reps: int = 5) -> float:
    import statistics

    import jax
    jax.block_until_ready(compiled(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        ts.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(ts)


def _lower(fn, *args):
    """Lower ``fn`` with the dispatch spy and the launch recorder on. The
    trace caches are cleared first: a kernel entry traced before (same
    shapes) would be reused without building its launch again, and the
    recorder would see nothing. Compiled programs held by the caller are
    unaffected."""
    import jax
    from repro.core import tsmm
    from repro.kernels import compat
    jax.clear_caches()
    with tsmm.record_dispatches() as events, \
            compat.capture_launches() as launches:
        lowered = fn.lower(*args)
    return lowered, events, launches


def _check_kernel_program(name, compiled, events, launches, kind):
    check(any(e.kind == kind and e.executor == "pallas-tpu"
              for e in events),
          f"{name}: no {kind} dispatch on pallas-tpu: {events}")
    check(launches, f"{name}: no Pallas launch was built")
    check(not any(c.interpret for c in launches),
          f"{name}: a launch was built with interpret=True")
    check("tpu_custom_call" in compiled.as_text(),
          f"{name}: compiled program holds no Mosaic kernel")


def kernel_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    key = jax.random.PRNGKey(seed)
    for i, (name, entry, kind, sa, sb) in enumerate(kernel_cases()):
        k1, k2 = jax.random.split(jax.random.fold_in(key, i))
        a = jax.random.normal(k1, sa, jnp.bfloat16)
        b = jax.random.normal(k2, sb, jnp.bfloat16)
        t0 = time.perf_counter()
        lowered, events, launches = _lower(kernel_fn(entry, False), a, b)
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
        _check_kernel_program(name, compiled, events, launches, kind)
        dense = kernel_fn(entry, True).lower(a, b).compile()
        check("tpu_custom_call" not in dense.as_text(),
              f"{name}: the dense arm compiled a Mosaic kernel")
        out, ref = compiled(a, b), dense(a, b)
        err = rel_err(out, ref)
        check(out.shape == ref.shape and bool(jnp.isfinite(out).all()),
              f"{name}: non-finite or misshapen output {out.shape}")
        check(err <= KERNEL_TOL,
              f"{name}: {kind} vs dense rel err {err:.3e} > {KERNEL_TOL}")
        _say(f"{name}: {kind} on pallas-tpu, rel err vs dense {err:.3e} "
             f"(tol {KERNEL_TOL}); smoke readings: compile "
             f"{compile_s:.2f}s, kernel {_timed_ms(compiled, a, b):.3f} ms, "
             f"dense {_timed_ms(dense, a, b):.3f} ms")
        del a, b, out, ref


def model_setup(seed: int):
    """rwkv6-1.6b config, bf16 params, prompts and an empty cache."""
    import jax
    import jax.numpy as jnp
    from repro.configs import registry
    from repro.models import model
    cfg = registry.get_config(MODEL_ARCH, smoke=False)
    key = jax.random.PRNGKey(seed)
    params = jax.jit(model.init, static_argnums=1)(key, cfg)
    # model.init zero-initializes each LoRA up-projection (the usual LoRA
    # start), which would multiply the tsm2r product by zero before it
    # reaches the logits. Draw it from the seed as well, so the check sees
    # the kernel's output.
    lora = params["segments"][0]["time_mix"]["w_lora"]
    rank = lora["b"].shape[-2]
    lora["b"] = (jax.random.normal(jax.random.fold_in(key, 1),
                                   lora["b"].shape, jnp.float32)
                 * rank ** -0.5).astype(lora["b"].dtype)
    prompts = jax.random.randint(jax.random.fold_in(key, 2),
                                 (BATCH, PROMPT), 0, cfg.vocab_size)
    cache = model.init_cache(cfg, BATCH, PROMPT + DECODE_STEPS + 1)
    return cfg, params, prompts, cache


def forward_logits_fn(cfg, start: int, stop: int):
    """Teacher-forced logits at positions [start, stop) of the tokens."""
    import jax
    from repro.models import model

    def f(params, tokens):
        x, _ = model.forward_hidden(params, cfg, {"tokens": tokens})
        return model.unembed_fn(params, cfg)(x[:, start:stop])
    return jax.jit(f)


def model_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core import tsmm
    from repro.serve import engine

    cfg, params, prompts, cache = model_setup(seed)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    _say(f"{MODEL_ARCH}: {n_params / 1e9:.3f} B params, d_model "
         f"{cfg.d_model}, {cfg.n_layers} layers, vocab {cfg.vocab_size}, "
         f"dtype {cfg.dtype}; batch {BATCH} x prompt {PROMPT}")
    batch = {"tokens": prompts}

    prefill, decode = engine.make_serve_fns(cfg)
    t0 = time.perf_counter()
    lowered, events, launches = _lower(jax.jit(prefill), params, batch,
                                       cache)
    prefill_c = lowered.compile()
    prefill_compile_s = time.perf_counter() - t0
    lora = [e for e in events if e.kind == "tsm2r"]
    check(lora and all(e.executor == "pallas-tpu" for e in lora),
          f"prefill dispatched no tsm2r on pallas-tpu: {events}")
    check(any(e.shape == (BATCH * PROMPT, cfg.d_model,
                          cfg.rwkv.decay_lora_rank) for e in lora),
          f"prefill tsm2r shapes {[e.shape for e in lora]}")
    _check_kernel_program("prefill", prefill_c, lora, launches, "tsm2r")

    dense_prefill, _ = engine.make_serve_fns(
        cfg, policy=tsmm.GemmPolicy(mode="dense"))
    dense_c = jax.jit(dense_prefill).lower(params, batch, cache).compile()

    logits, cache1 = prefill_c(params, batch, cache)
    logits_d, _ = dense_c(params, batch, cache)
    err = rel_err(logits, logits_d)
    check(bool(jnp.isfinite(logits).all()), "prefill logits not finite")
    check(err <= PREFILL_TOL,
          f"prefill logits vs dense arm rel err {err:.3e} > {PREFILL_TOL}")
    prefill_ms = _timed_ms(prefill_c, params, batch, cache, reps=3)
    _say(f"prefill: tsm2r on pallas-tpu; last-token logits vs dense arm "
         f"rel err {err:.3e} (tol {PREFILL_TOL}), rms "
         f"{rms_err(logits, logits_d):.3e}; smoke readings: compile "
         f"{prefill_compile_s:.1f}s, prefill {prefill_ms:.1f} ms "
         f"({BATCH * PROMPT} tokens)")
    del logits_d, dense_c

    t0 = time.perf_counter()
    pos0 = jnp.int32(PROMPT)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]
    decode_c = jax.jit(decode).lower(params, tok, pos0, cache1).compile()
    decode_compile_s = time.perf_counter() - t0
    step_logits, tokens = [logits], [tok]
    cache_t = cache1
    t0 = time.perf_counter()
    for i in range(DECODE_STEPS):
        lg, cache_t = decode_c(params, tok, jnp.int32(PROMPT + i), cache_t)
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)[:, None]
        step_logits.append(lg)
        tokens.append(tok)
    jax.block_until_ready(step_logits)
    decode_s = time.perf_counter() - t0

    # step_logits[j] predicts the token after position PROMPT - 1 + j.
    seq = jnp.concatenate([prompts] + tokens[:-1], axis=1)
    fwd = forward_logits_fn(cfg, PROMPT - 1, PROMPT + DECODE_STEPS)
    ref = fwd(params, seq)
    got = jnp.stack(step_logits, axis=1)
    check(got.shape == ref.shape, f"decode logits {got.shape} vs {ref.shape}")
    check(bool(jnp.isfinite(got).all()), "decode logits not finite")
    errs = [rel_err(got[:, j], ref[:, j]) for j in range(got.shape[1])]
    worst = max(errs)
    rms = max(rms_err(got[:, j], ref[:, j]) for j in range(got.shape[1]))
    check(worst <= DECODE_TOL,
          f"decode vs teacher-forced forward rel err {worst:.3e} > "
          f"{DECODE_TOL} (per step: {[f'{e:.2e}' for e in errs]})")
    _say(f"decode: {DECODE_STEPS} steps vs teacher-forced forward, worst "
         f"rel err {worst:.3e} (tol {DECODE_TOL}), worst rms {rms:.3e}; "
         f"smoke readings: compile "
         f"{decode_compile_s:.1f}s, {DECODE_STEPS / decode_s:.1f} steps/s = "
         f"{BATCH * DECODE_STEPS / decode_s:.1f} tokens/s at batch {BATCH}")


def sharded_phase(seed: int, chips: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.core import tsmm
    from repro.kernels import compat

    m, a_dim, b_dim = POWERSGD_SHAPE
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, (m, a_dim), jnp.bfloat16)
    y = jax.random.normal(k2, (m, b_dim), jnp.bfloat16)
    lowered, events, launches = _lower(kernel_fn("mmt", False), x, y)
    one = lowered.compile()
    _check_kernel_program("one-device tsmm_t", one, events, launches, "tsmt")
    ref = one(x, y)

    mesh = compat.make_mesh((chips,), ("data",))
    rows = NamedSharding(mesh, PartitionSpec("data", None))
    xs, ys = jax.device_put(x, rows), jax.device_put(y, rows)
    expect = {"psum": "shard_map", "psum_scatter": "shard_map-scatter"}
    for reduce_, executor in expect.items():
        with jax.set_mesh(mesh), tsmm.policy(reduce=reduce_):
            lowered, events, launches = _lower(kernel_fn("mmt", False),
                                               xs, ys)
            compiled = lowered.compile()
            out = compiled(xs, ys)
        name = f"{chips}-chip tsmm_t reduce={reduce_}"
        check(events[-1].executor == executor,
              f"{name}: outer executor {events[-1].executor}")
        shard = [e for e in events if e.executor == "pallas-tpu"]
        check(shard and all(e.kind == "tsmt"
                            and e.shape == (m // chips, a_dim, b_dim)
                            for e in shard),
              f"{name}: per-shard events {events}")
        _check_kernel_program(name, compiled, shard, launches, "tsmt")
        err = rel_err(np.asarray(out), ref)
        check(err <= SHARDED_TOL,
              f"{name}: vs one device rel err {err:.3e} > {SHARDED_TOL}")
        _say(f"{name}: {executor} over per-shard tsmt on pallas-tpu, rel err "
             f"vs one device {err:.3e} (tol {SHARDED_TOL}); smoke reading: "
             f"{_timed_ms(compiled, xs, ys):.3f} ms")


def train_phase(chips: int) -> None:
    from repro.core import tsmm
    from repro.launch import train
    argv = ["--arch", MODEL_ARCH, "--smoke", "--steps", "4",
            "--global-batch", "16", "--seq-len", "512",
            "--powersgd-rank", "4", "--log-every", "1"]
    with tsmm.record_dispatches() as events:
        multi = train.main(argv + ["--devices", str(chips)])
    sharded = {e.executor for e in events} & {"shard_map",
                                              "shard_map-scatter"}
    kernels = sorted({e.kind for e in events if e.executor == "pallas-tpu"})
    check(sharded and kernels,
          f"{chips}-chip train step reached no per-shard kernel: "
          f"{sorted({(e.kind, e.executor) for e in events})}")
    single = train.main(argv + ["--devices", "1"])
    lm, ls = multi["losses"], single["losses"]
    check(len(lm) == len(ls) == 4, f"losses {lm} vs {ls}")
    errs = [abs(p - q) / abs(q) for p, q in zip(lm, ls)]
    check(max(errs) <= TRAIN_TOL,
          f"{chips}-chip vs one-device PowerSGD losses {lm} vs {ls}")
    _say(f"train: {len(lm)} PowerSGD steps on the {chips}-chip data mesh "
         f"({'/'.join(sorted(sharded))} over per-shard {'/'.join(kernels)} "
         f"on pallas-tpu) vs one device, losses "
         f"{['%.5f' % v for v in lm]}, worst rel diff {max(errs):.2e} "
         f"(tol {TRAIN_TOL})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernel + model phases; 4: the data-parallel "
                         "path and its one-device comparison only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}; run this from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    sys.path.insert(0, HERE)

    from repro.launch.cache import configure_compilation_cache
    cache_dir = configure_compilation_cache()

    import jax
    from jax import monitoring

    devices = jax.devices()
    d0 = devices[0]
    _say(f"jax {jax.__version__}, platform {d0.platform}, device_kind "
         f"{d0.device_kind!r}, {len(devices)} device(s); compile cache "
         f"{cache_dir}")
    if d0.platform != "tpu":
        print("chip_smoke: JAX found no TPU; nothing was run",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 1

    cache_events = {"hits": 0, "misses": 0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_events["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            cache_events["misses"] += 1
    monitoring.register_event_listener(on_event)

    from repro.core import perf_model
    _say(f"kernel spec {perf_model.device_spec().name} (from device_kind)")
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            kernel_phase(args.seed)
            model_phase(args.seed)
        else:
            sharded_phase(args.seed, args.chips)
            train_phase(args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    stats = d0.memory_stats() or {}
    _say(f"smoke readings: wall {time.perf_counter() - t0:.1f}s, "
         f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'n/a')} "
         f"(of {stats.get('bytes_limit', 'n/a')}), compile cache hits "
         f"{cache_events['hits']}, misses {cache_events['misses']}")
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
