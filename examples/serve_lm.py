"""Batched serving example: prefill a batch of prompts, decode with greedy
and temperature sampling, verify the KV-cache path against the full
forward (the correctness invariant behind decode_32k / long_500k cells).

    PYTHONPATH=src python examples/serve_lm.py --arch mixtral-8x7b
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.launch.cache import configure_compilation_cache
from repro.models import model
from repro.serve import engine


def main():
    configure_compilation_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-3b",
                    choices=[a for a in registry.ARCH_NAMES
                             if a != "hubert-xlarge"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=16)
    args = ap.parse_args()

    cfg = registry.get_config(args.arch, smoke=True)
    key = jax.random.PRNGKey(0)
    params = model.init(key, cfg)
    prompts = jax.random.randint(jax.random.fold_in(key, 1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    extras = None
    if cfg.family == "vlm":
        extras = {"image_embeds": jax.random.normal(
            jax.random.fold_in(key, 2),
            (args.batch, cfg.vision_seq, cfg.vision_dim))}

    t0 = time.time()
    out_greedy = engine.generate(params, cfg, prompts, args.max_new,
                                 extras=extras)
    t1 = time.time()
    out_sampled = engine.generate(params, cfg, prompts, args.max_new,
                                  temperature=0.8, extras=extras,
                                  key=jax.random.PRNGKey(7))
    print(f"[serve] {args.arch} batch={args.batch} prompt={args.prompt_len} "
          f"new={args.max_new}")
    print(f"  greedy tokens[0]: {np.asarray(out_greedy[0])}")
    print(f"  sampled tokens[0]: {np.asarray(out_sampled[0])}")
    print(f"  prefill+decode wall: {t1 - t0:.2f}s "
          f"({args.batch * args.max_new / (t1 - t0):.1f} tok/s incl. compile)")

    # correctness: greedy continuation == argmax over the teacher-forced
    # full forward at each position
    full_tokens = jnp.concatenate([prompts, out_greedy], axis=1)
    batch = {"tokens": full_tokens}
    if extras:
        batch.update(extras)
    logits, _ = model.forward(params, cfg, batch)
    for t in range(args.max_new):
        pos = args.prompt_len + t - 1
        expect = jnp.argmax(logits[:, pos], -1)
        np.testing.assert_array_equal(np.asarray(out_greedy[:, t]),
                                      np.asarray(expect))
    print("  KV-cache decode == teacher-forced forward: OK")


if __name__ == "__main__":
    main()
