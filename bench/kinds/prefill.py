"""Traffic kind ``prefill``: a closed loop of prompt batches to their first
token.

Mix parameters: ``batch`` prompts of ``prompt_len`` tokens per round;
``check_requests`` finished requests compared with the reference.

The entry is ``serve.engine.make_serve_fns(cfg)[0]`` (prefill into a fresh
cache) with greedy first-token sampling, jitted once at the mix's shape.
Each round hands ``batch`` fresh seeded prompts to it and waits for their
first tokens on the host; its time is that round's time to first token.
The entry also returns the last-position logits the tokens were sampled
from, which stay on the device until the window has closed; the check
compares them and the tokens with the reference (``logit_err`` and
``token_gap_excess``, see ``check.py``).
"""

from __future__ import annotations

import time

import numpy as np

from bench import check, serving


class Generator:
    def __init__(self, mix: dict, cfg_mod, sizes: dict, seed: int):
        self.mix, self.cfg_mod, self.sizes, self.seed = mix, cfg_mod, sizes, seed
        self.batch, self.length = mix["batch"], mix["prompt_len"]
        self._served, self._logits = [], []

    def prompts(self, index: int, stream: int = serving.PROMPT_STREAM) -> np.ndarray:
        return serving.prompts(self.seed, stream, index, self.batch, self.length,
                               self.sizes["vocab_size"])

    def build(self, cfg) -> None:
        import jax
        from repro.models import model
        from repro.serve import engine

        prefill_step, _ = engine.make_serve_fns(cfg)
        batch, length = self.batch, self.length

        def prefill(params, tokens):
            cache = model.init_cache(cfg, batch, length)
            logits, cache = prefill_step(params, {"tokens": tokens}, cache)
            return engine.sample_token(None, logits), logits, cache

        self._prefill = jax.jit(prefill)

    def setup(self, params) -> serving.Record:
        """Warm the one shape of the window."""
        tok, _, _ = self._prefill(params, self.prompts(0, serving.WARMUP_STREAM))
        np.asarray(tok)
        return serving.Record("prefill")

    def window(self, params, rec: serving.Record, seconds: float) -> serving.Record:
        """Every round started before the deadline runs to its end and
        counts."""
        from jax.profiler import TraceAnnotation
        t0 = time.perf_counter()
        deadline = t0 + seconds
        with TraceAnnotation("bench.window"):
            while time.perf_counter() < deadline:
                t_round = time.perf_counter()
                with TraceAnnotation("bench.prompts"):
                    toks = self.prompts(rec.rounds)
                t_in = time.perf_counter()
                with TraceAnnotation("bench.call"):
                    out, logits, _ = self._prefill(params, toks)
                with TraceAnnotation("bench.to_host"):
                    first = np.asarray(out)
                t_out = time.perf_counter()
                rec.ttft_s.append(t_out - t_in)
                rec.round_s.append(t_out - t_round)
                self._served.append(first)
                self._logits.append(logits)
                rec.rounds += 1
        rec.seconds = time.perf_counter() - t0
        rec.tokens = rec.rounds * self.batch * self.length
        rec.attempted = rec.rounds * self.batch
        return rec

    def release(self) -> None:
        """Keep the sampled requests' logits on the host, free the rest."""
        idx = self._sample()
        rows = {}
        for i in idx:
            r = i // self.batch
            if r not in rows:
                rows[r] = np.asarray(self._logits[r], np.float32)
        self._got = np.stack([rows[i // self.batch][i % self.batch] for i in idx]) \
            if len(idx) else None
        self._logits = []
        self._prefill = None

    def _sample(self) -> np.ndarray:
        return check.sample(self.seed, len(self._served) * self.batch,
                            self.mix["check_requests"])

    def numbers(self, params, lowp=None):
        """(the program's numbers, the control's or None)."""
        idx = self._sample()
        per = self.batch
        seqs = np.stack([self.prompts(i // per)[i % per] for i in idx])
        served = np.stack([self._served[i // per][i % per] for i in idx])
        return check.last_position_numbers(self.cfg_mod, self.sizes, params, seqs,
                                           self._got, served, lowp)

    def flops(self, rec: serving.Record) -> float:
        """The configuration's FLOPs of the window's work."""
        return rec.rounds * self.cfg_mod.prefill_flops(self.sizes, self.batch,
                                                       self.length)
