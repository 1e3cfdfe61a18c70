"""What every traffic kind shares: seeded prompts and keys, the program's
configuration and weights, the record of a window, and the compile
counter.

A traffic mix (``traffic/<mix>.json``) names its ``kind``; the kind is the
file ``kinds/<kind>.py``, whose ``Generator`` builds the program's entries,
warms them up, runs the window and reads the numbers that decide
``correct``. Nothing here names a kind.

Prompts are token ids drawn uniformly from the vocabulary by numpy from
(seed, stream, round), so the same seed gives the same prompts in every
run.
"""

from __future__ import annotations

import dataclasses
import gc
import statistics
import time

import numpy as np

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
PROMPT_STREAM, WARMUP_STREAM = 1, 2


def jax_key(seed: int):
    """A JAX key from a seed of any size (both 32-bit halves used)."""
    import jax
    u = seed % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(u & 0xFFFFFFFF), u >> 32)


def prompts(seed: int, stream: int, index: int, batch: int, length: int,
            vocab: int) -> np.ndarray:
    rng = np.random.default_rng([seed % (1 << 64), stream, index])
    return rng.integers(0, vocab, (batch, length), dtype=np.int32)


# ---------------------------------------------------------------------------
# The program
# ---------------------------------------------------------------------------

def _replace(obj, fields: dict):
    changes = {k: _replace(getattr(obj, k), v) if isinstance(v, dict) else v
               for k, v in fields.items()}
    return dataclasses.replace(obj, **changes)


def program_config(cfg_mod, sizes):
    """The program's ``ModelConfig`` with every field the sizes set."""
    from repro.configs import registry
    arch, fields = cfg_mod.program(sizes)
    return _replace(registry.get_config(arch), fields)


def build_params(cfg_mod, sizes, seed: int):
    """Weights from the seed, on the device, in one jitted call."""
    import jax
    return jax.jit(lambda k: cfg_mod.make_params(k, sizes))(jax_key(seed))


def check_layout(params, cfg) -> None:
    """Refuse weights whose tree, shapes or dtypes differ from the
    program's own ``model.init``."""
    import functools

    import jax
    from repro.models import model
    want = jax.eval_shape(functools.partial(model.init, cfg=cfg),
                          jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree.structure(want) != jax.tree.structure(got):
        raise ValueError("weights' tree differs from model.init's:\n"
                         f"{jax.tree.structure(got)}\nvs\n{jax.tree.structure(want)}")
    bad = [(jax.tree_util.keystr(p), g, w) for (p, g), w in
           zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want))
           if (g.shape, g.dtype) != (w.shape, w.dtype)]
    if bad:
        raise ValueError(f"weights differ from model.init's at {bad[:5]}")


class CompileCounter:
    """Counts traces and backend compiles while ``active``."""

    def __init__(self):
        self.count = 0
        self.active = False

    def __call__(self, event, duration_secs, **_):
        if self.active and event in COMPILE_EVENTS:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self)


class GcPauses:
    """Times the cyclic garbage collector's passes while ``active``."""

    def __init__(self):
        self.pauses = []
        self.active = False
        self._t0 = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self.active and self._t0 is not None:
            self.pauses.append((info["generation"], time.perf_counter() - self._t0))

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)

    def summary(self) -> str:
        longest = max((t for _, t in self.pauses), default=0.0)
        full = sum(1 for g, _ in self.pauses if g == 2)
        return (f"{len(self.pauses)} collector passes ({full} full), longest "
                f"{longest * 1e3:.3f} ms, {sum(t for _, t in self.pauses):.3f} s in all")


def freeze_heap() -> None:
    """Move every object the set-up left (traced programs, caches,
    modules) out of the cyclic collector's reach, as a long-running server
    does after its warm-up: a full pass over them stalls the host for
    about a tenth of a second each time, and the window's own garbage is
    still collected. ``thaw_heap`` undoes it once the window has closed."""
    gc.collect()
    gc.freeze()


def thaw_heap() -> None:
    gc.unfreeze()


# ---------------------------------------------------------------------------
# The window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Record:
    """What a window did, in the terms the metric readers use."""
    kind: str
    seconds: float = 0.0
    tokens: int = 0                 # the work the kind's rate counts
    attempted: int = 0
    failed: int = 0
    rounds: int = 0                 # batches or steps
    ttft_s: list = dataclasses.field(default_factory=list)
    round_s: list = dataclasses.field(default_factory=list)   # host time per round

    def stalls(self) -> str:
        """One line on the slowest rounds, to tell a slow stretch of the
        host or device from a slow window."""
        if not self.round_s:
            return "no rounds"
        med = statistics.median(self.round_s)
        slow = sorted(self.round_s, reverse=True)[:3]
        over = [t for t in self.round_s if t > 2 * med]
        return (f"{len(self.round_s)} rounds, median {med * 1e3:.3f} ms, slowest "
                f"{[round(t * 1e3, 3) for t in slow]} ms; {len(over)} over twice "
                f"the median, {sum(t - med for t in over):.3f} s beyond it")
