"""Traffic kind ``decode``: streaming sessions, one token each per step.

Mix parameters: ``batch`` sessions prefilled with ``prompt_len``-token
prompts in set-up (set-up the traffic needs); ``max_new`` sizes the cache
and caps the steps; ``check_sequences`` whole sessions compared with the
reference.

The entries are ``serve.engine.make_serve_fns(cfg)``: prefill for the
set-up, then the decode step with greedy sampling, jitted once at the
mix's shape with the cache donated. The window runs the decode step after
step as a streaming server does, one step in flight: step n + 1 is handed
to the device with step n's tokens still on it, then step n's tokens are
read back to the host. A round is one step's tokens reaching the host.
The check compares every served token of the sampled sessions
(``max_gap``, see ``check.py``).
"""

from __future__ import annotations

import time

import numpy as np

from bench import check, serving

WARMUP_STEPS = 2


class Generator:
    def __init__(self, mix: dict, cfg_mod, sizes: dict, seed: int):
        self.mix, self.cfg_mod, self.sizes, self.seed = mix, cfg_mod, sizes, seed
        self.batch, self.length = mix["batch"], mix["prompt_len"]
        self.max_len = self.length + mix["max_new"]
        self._served = []

    def build(self, cfg) -> None:
        import jax
        from repro.models import model
        from repro.serve import engine

        prefill_step, decode_step = engine.make_serve_fns(cfg)
        batch, max_len = self.batch, self.max_len

        def prefill(params, tokens):
            cache = model.init_cache(cfg, batch, max_len)
            logits, cache = prefill_step(params, {"tokens": tokens}, cache)
            return engine.sample_token(None, logits), cache

        def decode(params, tok, pos, cache):
            logits, cache = decode_step(params, tok[:, None], pos, cache)
            return engine.sample_token(None, logits), cache

        self._prefill = jax.jit(prefill)
        self._decode = jax.jit(decode, donate_argnums=(3,))

    def setup(self, params) -> serving.Record:
        """Prefill the sessions, then warm the decode step."""
        self._prompts = serving.prompts(self.seed, serving.PROMPT_STREAM, 0,
                                        self.batch, self.length,
                                        self.sizes["vocab_size"])
        self._tok, self._cache = self._prefill(params, self._prompts)
        self._served.append(np.asarray(self._tok))
        self._pos = self.length
        self._steps(params, serving.Record("decode"), None, WARMUP_STEPS)
        self.first_pos = self._pos
        return serving.Record("decode")

    def _steps(self, params, rec, deadline, limit=None):
        """Steps until the deadline (or ``limit`` steps), one in flight."""
        from jax.profiler import TraceAnnotation
        pending, t_last, n = None, time.perf_counter(), 0
        while (self._pos < self.max_len and (limit is None or n < limit)
               and (deadline is None or time.perf_counter() < deadline)):
            with TraceAnnotation("bench.call"):
                self._tok, self._cache = self._decode(
                    params, self._tok, np.int32(self._pos), self._cache)
            self._pos += 1
            n += 1
            if pending is not None:
                t_last = self._arrive(pending, rec, t_last)
            pending = self._tok
        if pending is not None:
            self._arrive(pending, rec, t_last)

    def _arrive(self, tok, rec, t_last) -> float:
        from jax.profiler import TraceAnnotation
        with TraceAnnotation("bench.to_host"):
            self._served.append(np.asarray(tok))
        now = time.perf_counter()
        rec.round_s.append(now - t_last)
        rec.rounds += 1
        return now

    def window(self, params, rec: serving.Record, seconds: float) -> serving.Record:
        from jax.profiler import TraceAnnotation
        t0 = time.perf_counter()
        with TraceAnnotation("bench.window"):
            self._steps(params, rec, t0 + seconds)
        rec.seconds = time.perf_counter() - t0
        rec.tokens = rec.attempted = rec.rounds * self.batch
        return rec

    def release(self) -> None:
        self._cache = self._tok = None
        self._prefill = self._decode = None

    def numbers(self, params, lowp=None):
        """(the program's numbers, the control's or None)."""
        served = np.stack(self._served, axis=1)               # (batch, N)
        idx = check.sample(self.seed, self.batch, self.mix["check_sequences"])
        seqs = np.concatenate([self._prompts[idx], served[idx, :-1]], axis=1)
        prog, ctl = check.token_gaps(self.cfg_mod, self.sizes, params, seqs,
                                     served[idx], lowp)
        return ({"max_gap": float(prog.max())},
                None if ctl is None else {"max_gap": float(ctl.max())})

    def _positions(self, rec):
        return range(self.first_pos, self.first_pos + rec.rounds)

    def flops(self, rec: serving.Record) -> float:
        """The configuration's FLOPs of the window's steps."""
        return sum(self.cfg_mod.decode_flops(self.sizes, self.batch, pos)
                   for pos in self._positions(rec))

    def moved_bytes(self, rec: serving.Record, param_bytes: int) -> float:
        """Bytes the window's steps must move (``decode_bytes``)."""
        return sum(self.cfg_mod.decode_bytes(self.sizes, param_bytes, self.batch, pos)
                   for pos in self._positions(rec))
