"""Record the small device trace that ``test_bench_trace.py`` reads.

Run on one TPU from the checkout root:

    python bench/tests/record_small_trace.py [out_dir]

It traces three rounds of one tsm2r call at the rwkv6 decay-LoRA shape
(4096 x 2048 x 64, bf16, through ``repro.core.tsmm``), one XLA dot and a
5 ms host pause, copies the ``.xplane.pb`` to ``out_dir`` (default
``bench/tests/data``) as ``small.xplane.pb``, and prints the device
planes, their lines and the events' names so the reduction in
``bench/trace.py`` can be checked against them.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from repro.core import tsmm

    if jax.default_backend() != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    out_dir = sys.argv[1] if len(sys.argv) > 1 else os.path.join(ROOT, "bench", "tests", "data")
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(k1, (4096, 2048), jnp.bfloat16)
    b = jax.random.normal(k2, (2048, 64), jnp.bfloat16)
    c = jax.random.normal(k2, (1024, 1024), jnp.bfloat16)
    lora = jax.jit(lambda x, y: tsmm.tsmm(x, y))
    dot = jax.jit(lambda x: x @ x)
    with tsmm.record_dispatches() as log:
        lora(a, b).block_until_ready()
    print("dispatches", [(e.kind, e.executor, e.shape) for e in log])
    dot(c).block_until_ready()
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(3):
            lora(a, b).block_until_ready()
            dot(c).block_until_ready()
            time.sleep(0.005)
        jax.profiler.stop_trace()
        src = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))[0]
        os.makedirs(out_dir, exist_ok=True)
        dst = os.path.join(out_dir, "small.xplane.pb")
        shutil.copy(src, dst)
    print("wrote", dst, os.path.getsize(dst))
    for plane in ProfileData.from_file(dst).planes:
        lines = list(plane.lines)
        print("PLANE", plane.name, [(ln.name, len(list(ln.events))) for ln in lines])
        if not plane.name.startswith("/device"):
            continue
        for ln in lines:
            for e in list(ln.events)[:40]:
                stats = [(k, str(v)[:80]) for k, v in e.stats]
                print("  ", ln.name, "|", e.name, e.start_ns, e.duration_ns, stats)
    return 0


if __name__ == "__main__":
    sys.exit(main())
