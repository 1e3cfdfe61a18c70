"""Record the small scoped trace that ``test_bench_scopes.py`` reads.

Run on one TPU from the checkout root:

    python bench/tests/record_scoped_trace.py [out_dir]

It traces three calls of a two-layer scanned program, each layer a
``block`` adding ``layers.lora_apply`` of the rwkv6 decay-LoRA shape
(4096 x 2048 x 64, bf16: the first half reaches tsm2r), and writes to
``out_dir`` (default ``bench/tests/data``) the trace as
``scoped.xplane.pb`` and the program's optimized HLO, without its
source tables, as ``scoped.hlo.txt``; then prints the device ops with
the scope path ``bench/scopes.py`` gives each.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def _without_source_tables(text: str) -> str:
    """The module text without its file, function and stack-frame tables,
    which name the files of the machine that compiled it; the reader needs
    only the computations."""
    lines = text.splitlines(keepends=True)
    first = next(i for i, ln in enumerate(lines) if ln.startswith(("%", "ENTRY")))
    return lines[0] + "\n" + "".join(lines[first:])


def main() -> int:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from bench import scopes, tracing
    from repro.core import tsmm
    from repro.models import layers

    if jax.default_backend() != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    out_dir = sys.argv[1] if len(sys.argv) > 1 else os.path.join(ROOT, "bench", "tests", "data")
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(k1, (4096, 2048), jnp.bfloat16)
    lora = {"a": jax.random.normal(k2, (2, 2048, 64), jnp.bfloat16) * 0.02,
            "b": jax.random.normal(k3, (2, 64, 2048), jnp.bfloat16) * 0.02}

    @layers.scoped("block")
    def block(h, w):
        return h + layers.lora_apply(w, h), None

    @jax.jit
    def step(x, lora):
        with jax.named_scope("layers"):
            return lax.scan(block, x, lora)[0]

    with tsmm.record_dispatches() as log:
        step(x, lora).block_until_ready()
    print("dispatches", [(e.kind, e.executor, e.shape) for e in log])
    text = _without_source_tables(step.lower(x, lora).compile().as_text())
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(3):
            step(x, lora).block_until_ready()
        jax.profiler.stop_trace()
        src = glob.glob(os.path.join(tmp, "plugins", "profile", "*", "*.xplane.pb"))[0]
        os.makedirs(out_dir, exist_ok=True)
        dst = os.path.join(out_dir, "scoped.xplane.pb")
        shutil.copy(src, dst)
    with open(os.path.join(out_dir, "scoped.hlo.txt"), "w") as f:
        f.write(text)
    print("wrote", dst, os.path.getsize(dst), "and scoped.hlo.txt", len(text))
    trace = tracing.read(dst)
    ins = scopes.parse(text)
    for e in trace.ops_in_window(trace.devices[0])[:40]:
        i = ins.get(tracing.op_name(e.name))
        print(f"  {e.dur_ns:10.0f} ns  {tracing.op_name(e.name):40s} "
              f"{'/'.join(scopes.scope_path(i.op_name)) if i else 'NOT IN MODULE'}")
    att = scopes.attribute(trace, ins, scopes.module_name(text))
    print("matched", None if att is None else att.matched_share())
    if att is not None:
        print(scopes.report(att, 3, scopes.module_name(text)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
