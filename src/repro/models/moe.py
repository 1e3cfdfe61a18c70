"""Mixture-of-Experts: top-k routing over every expert, dropless compute of
the experts this layer holds.

Routers:
* ``softmax`` (Mixtral): softmax over E, top-k, renormalize selected.
* ``sigmoid`` (DeepSeek-V3, ``noaux_tc``): sigmoid scores in f32; the
  aux-loss-free balancing bias is added for *selection only*. Selection is
  group-limited: the E experts form ``n_group`` groups, a group scores the
  sum of its two best biased scores, the ``topk_group`` best groups are
  kept, and the top-k biased scores among their experts are chosen. The
  combine weights are the unbiased scores of the chosen experts,
  renormalized to sum 1.
Both scale the combine weights by ``routed_scale`` (DeepSeek-V3: 2.5).

Held share (expert parallelism): the layer holds experts
``[first_held, first_held + n_held)`` of the ``n_experts`` it routes over,
and computes only their part of the routed output; the shared expert is
added whole. One rank of an EP deployment runs exactly this; with every
expert held it is the whole layer.

Dropless dispatch: the (token, expert) pairs that land on held experts are
sorted by expert, their tokens gathered into a buffer, and each held
expert's SwiGLU runs as a grouped matmul over its contiguous rows
(megablox's ``gmm``, which visits only the row tiles its groups hold);
each (token, slot) pair reads its row back through the inverse of the
sort and a token sums its ``k`` rows. A token picks distinct experts, so
at most ``T * min(k, n_held)`` pairs are held. The buffer holds ``SLACK`` times the pairs an even routing sends here, and a
batch that sends more takes the buffer of every possible pair, so no
token is dropped even when all of them pick one held expert.

Capacity dispatch (``capacity_factor`` set; the GSPMD-sharded train and
dry-run paths, which hold every expert): GShard-style, within
``dispatch_groups`` groups of tokens (one per data shard, so the sort and
scatter stay shard-local) each expert takes at most ``capacity_factor``
times an even share of its group's pairs into a dense (G, E, C, d) buffer,
and pairs past that fall through on the residual. Its per-expert einsums
stay single GSPMD ops that the expert-parallel sharding partitions, which
the grouped matmul's custom call is not.

Profile scopes: ``moe`` around the layer; beneath it ``router`` (router
projection and selection), ``dispatch`` (sort, gather, combine),
``experts`` (the grouped matmuls and their SwiGLU); the shared expert is a
``swiglu`` of ``dense`` projections.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas.ops.tpu.megablox import gmm

from repro.kernels import compat
from repro.models import layers


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_ff_expert: int
    n_shared: int = 0
    d_ff_shared: int = 0          # defaults to d_ff_expert * n_shared
    router: str = "softmax"        # 'softmax' | 'sigmoid'
    routed_scale: float = 1.0      # DeepSeek scales routed output by 2.5
    # Group-limited selection (DeepSeek-V3 noaux_tc: 8 groups, keep 4).
    n_group: int = 1
    topk_group: int = 1
    # Experts held here: [first_held, first_held + n_held); n_held defaults
    # to n_experts, every expert.
    first_held: int = 0
    n_held: Optional[int] = None
    # Capacity dispatch (GSPMD-sharded train and dry-run paths only; None is
    # dropless): pairs past capacity_factor x an even share are dropped.
    capacity_factor: Optional[float] = None
    # Dispatch groups: tokens route within their group only (set to the DP
    # shard count so sort/scatter stay shard-local under GSPMD -- a global
    # argsort over the sharded token axis otherwise gathers the world:
    # 224 GiB/device measured on deepseek-v3 prefill_32k).
    dispatch_groups: int = 1

    def __post_init__(self):
        if self.n_held is None:
            object.__setattr__(self, "n_held", self.n_experts)

    @property
    def held(self) -> tuple[int, int]:
        """(first held expert, number held)."""
        return self.first_held, self.n_held


def for_gspmd(cfg: MoEConfig, data_shards: int, tokens: int) -> MoEConfig:
    """The layer as the GSPMD-sharded train and dry-run paths run it:
    capacity dispatch at GShard's factor 1.25, one dispatch group per data
    shard (one group if the tokens do not divide)."""
    groups = data_shards if tokens % data_shards == 0 else 1
    return dataclasses.replace(cfg, capacity_factor=1.25, dispatch_groups=groups)


def moe_init(key, d_model: int, cfg: MoEConfig, dtype):
    ks = jax.random.split(key, 5)
    e, f = cfg.n_experts, cfg.d_ff_expert
    eh = cfg.held[1]
    params = {
        "router_w": layers.dense_init(ks[0], d_model, e, jnp.float32),
        "router_bias": jnp.zeros((e,), jnp.float32),
        # nested under "experts" so sharding rules can EP-shard these and
        # TP-shard dense "ffn/w_*" without path ambiguity
        "experts": {
            "w_gate": (jax.random.normal(ks[1], (eh, d_model, f), jnp.float32)
                       * d_model ** -0.5).astype(dtype),
            "w_up": (jax.random.normal(ks[2], (eh, d_model, f), jnp.float32)
                     * d_model ** -0.5).astype(dtype),
            "w_down": (jax.random.normal(ks[3], (eh, f, d_model), jnp.float32)
                       * f ** -0.5).astype(dtype),
        },
    }
    if cfg.n_shared:
        d_sh = cfg.d_ff_shared or cfg.d_ff_expert * cfg.n_shared
        params["shared"] = layers.swiglu_init(ks[4], d_model, d_sh, dtype)
    return params


def _top_k(x, k: int):
    """``lax.top_k`` over the last axis (values descending, the lower index
    first on ties) as ``k`` passes of argmax: a few VPU reductions where
    the TPU's ``top_k`` sorts each row."""
    iota = lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
    vals, idxs = [], []
    for _ in range(k):
        i = jnp.argmax(x, axis=-1).astype(jnp.int32)
        vals.append(jnp.max(x, axis=-1))
        idxs.append(i)
        x = jnp.where(iota == i[..., None], -jnp.inf, x)
    return jnp.stack(vals, -1), jnp.stack(idxs, -1)


def _group_limited(biased, cfg: MoEConfig):
    """``biased`` (T, E) with the experts outside each token's
    ``topk_group`` best groups set to -inf (a group scores the sum of its
    two best entries)."""
    if cfg.n_group == 1:
        return biased
    t, e = biased.shape
    grouped = biased.reshape(t, cfg.n_group, e // cfg.n_group)
    group_score = _top_k(grouped, 2)[0].sum(-1)                    # (T, G)
    _, keep = _top_k(group_score, cfg.topk_group)
    kept = jax.nn.one_hot(keep, cfg.n_group, dtype=jnp.bool_).any(1)  # (T, G)
    return jnp.where(kept[:, :, None], grouped, -jnp.inf).reshape(t, e)


def route(params, xt, cfg: MoEConfig):
    """xt: (T, d) -> (combine weights (T,k) f32, expert ids (T,k) i32,
    probs (T,E)). The weights carry ``routed_scale``."""
    logits = layers.dense(params["router_w"].astype(xt.dtype), xt).astype(jnp.float32)
    if cfg.router == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        biased = scores + params["router_bias"][None, :]   # bias: selection only
        _, idx = _top_k(_group_limited(biased, cfg), cfg.top_k)
        w = jnp.take_along_axis(scores, idx, axis=1)
        probs = scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-9)
    else:
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = _top_k(probs, cfg.top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return w * cfg.routed_scale, idx, probs


# Rows of the grouped matmul's tiles; gate/up and down stream their weights
# in (TK, TN) tiles (the chip's timing: PERF.md).
TM, TK, TN = 256, 512, 2048
# The buffer of held pairs is this many times the rows a balanced routing
# sends here; a batch that sends more takes a buffer of every possible pair.
SLACK = 2


def _grouped(lhs, rhs, sizes):
    """Rows of ``lhs`` (m, a) in contiguous groups of ``sizes`` (g,), each
    times its ``rhs`` (g, a, b): megablox's grouped matmul, which visits
    only the row tiles its groups hold (rows past them are left unwritten)."""
    tiling = (TM, min(TK, rhs.shape[1]), min(TN, rhs.shape[2]))
    return gmm(lhs, rhs, sizes, lhs.dtype, tiling, None, None, False,
               compat.auto_interpret(None))


def _buffer_rows(local, eh: int, m: int):
    """(the pair each of ``m`` buffer rows holds, pairs per held expert):
    the (token, slot) pairs whose expert ``local`` (T*k,; relative to the
    first held) is held, sorted by expert, come first; the rows past them
    hold pairs that are not held."""
    key = jnp.where((local >= 0) & (local < eh), local, eh)
    sizes = (key[:, None] == jnp.arange(eh)).sum(0, dtype=jnp.int32)
    order = jnp.argsort(key, stable=True)
    return jnp.pad(order, (0, max(m - order.shape[0], 0)))[:m], sizes


def _held_part(params, xt, w, idx, cfg: MoEConfig, m: int):
    """The held experts' part of the routed output (T, d), through a buffer
    of ``m`` rows that holds every held (token, expert) pair."""
    t, k = idx.shape
    e0, eh = cfg.held
    with jax.named_scope("dispatch"):
        pair, sizes = _buffer_rows((idx - e0).reshape(-1), eh, m)
        filled = (jnp.arange(m) < sizes.sum())[:, None]
        tok = pair // k
        rows = xt[tok]
        wt = w.reshape(-1)[pair][:, None]

    def grouped(lhs, rhs):
        # The grouped matmul leaves rows past the groups unwritten, in its
        # output and in its input's gradient: zero them on both sides.
        return jnp.where(filled, _grouped(jnp.where(filled, lhs, 0), rhs, sizes), 0)

    with jax.named_scope("experts"):
        ew = params["experts"]
        h = jax.nn.silu(grouped(rows, ew["w_gate"])) * grouped(rows, ew["w_up"])
        # The combine weight scales each row before the down projection.
        y = grouped((h * wt).astype(xt.dtype), ew["w_down"])
    with jax.named_scope("dispatch"):
        return _combine(y, pair, filled, t, k).astype(xt.dtype)


def _combine(y, pair, filled, t: int, k: int):
    """Each token's sum (T, d) f32 of its rows of ``y`` (m, d): every
    (token, slot) pair reads its row through the inverse of the sort
    (``pair``: the pair each row holds; a pair not held reads a zero row),
    and the token sums its ``k``. T*k*d work, where a one-hot product is
    T*m*d and a scatter-add of rows serializes on the TPU (PERF.md)."""
    m = y.shape[0]
    row = jnp.arange(m, dtype=jnp.int32)
    inv = jnp.full((t * k,), m, jnp.int32).at[
        jnp.where(filled[:, 0], pair, t * k)].set(row, mode="drop",
                                                  unique_indices=True)
    y = jnp.concatenate([y, jnp.zeros((1, y.shape[1]), y.dtype)])
    return y[inv].reshape(t, k, -1).sum(1, dtype=jnp.float32)


@layers.scoped("moe")
def moe_fwd(params, x, cfg: MoEConfig):
    """x: (B, S, d). Returns (out, metrics dict): the shared expert plus
    the held experts' part of the routed output, no token dropped (with
    ``capacity_factor`` set, the capacity dispatch of every expert)."""
    if cfg.capacity_factor is not None:
        return _capacity_fwd(params, x, cfg)
    b, s, d = x.shape
    xt = x.reshape(-1, d)
    t, k = xt.shape[0], cfg.top_k
    e0, eh = cfg.held

    def rows(n):
        return -(-n // TM) * TM

    full = rows(t * min(k, eh))     # a token picks distinct experts
    small = min(full, rows(SLACK * -(-t * k * eh // cfg.n_experts)))
    with jax.named_scope("router"):
        w, idx, probs = route(params, xt, cfg)
    if small == full:
        out = _held_part(params, xt, w, idx, cfg, full)
    else:
        n_held = ((idx >= e0) & (idx < e0 + eh)).sum()
        out = lax.cond(n_held <= small,
                       functools.partial(_held_part, cfg=cfg, m=small),
                       functools.partial(_held_part, cfg=cfg, m=full),
                       params, xt, w, idx)
    if cfg.n_shared:
        out = out + layers.swiglu(params["shared"], xt)

    # Switch-style load-balance diagnostics over all E (metric; DeepSeek uses
    # the aux-loss-free router-bias update instead -- see update_router_bias).
    e = cfg.n_experts
    frac_tokens = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0) / (t * k)
    metrics = {
        "moe_balance_loss": e * jnp.sum(frac_tokens * probs.mean(axis=0)),
        "moe_max_load": frac_tokens.max() * e,
    }
    return out.reshape(b, s, d), metrics


def _dispatch_indices(se, stok, sw, e: int, cap: int):
    """One group's sorted entries -> (tok_buf (E*C,), w_buf (E*C,), keep).

    Index-based: only int32 indices and f32 weights are scattered; the
    activation gather happens later at (E, C, d) granularity, so no
    (T*k, d) data tensor ever materializes.
    """
    tk = se.shape[0]
    starts = jnp.searchsorted(se, jnp.arange(e))
    rank = jnp.arange(tk) - starts[se]
    keep = rank < cap
    dest = jnp.where(keep, se * cap + rank, e * cap)   # OOB slot drops
    sentinel = stok.shape[0]  # index of the zero pad row in xt_pad
    tok_buf = jnp.full((e * cap,), sentinel, jnp.int32).at[dest].set(
        stok.astype(jnp.int32), mode="drop", unique_indices=True)
    w_buf = jnp.zeros((e * cap,), jnp.float32).at[dest].set(
        sw * keep, mode="drop", unique_indices=True)
    return tok_buf, w_buf, keep


def _capacity_fwd(params, x, cfg: MoEConfig):
    """Capacity dispatch of every expert (module docstring).

    Group-local (cfg.dispatch_groups = DP shard count): within each group,
    entries sort by expert, ranks clip to capacity, and int32 index buffers
    address a (G, E, C, d) gather -- all shard-local under GSPMD; only the
    expert einsum touches the 'model' axis (EP).
    """
    from repro.distributed.sharding import maybe_wsc

    e = cfg.n_experts
    if cfg.held != (0, e):
        raise ValueError("capacity dispatch holds every expert")
    b, s, d = x.shape
    t = b * s
    ng = cfg.dispatch_groups if t % cfg.dispatch_groups == 0 else 1
    tl = t // ng                                     # tokens per group
    xt = x.reshape(t, d)
    with jax.named_scope("router"):
        w, idx, probs = route(params, xt, cfg)

    k = cfg.top_k
    cap = max(8, int(cfg.capacity_factor * tl * k / e))
    dp = ("pod", "data")
    with jax.named_scope("dispatch"):
        # Per-group flatten + stable sort by expert.
        ge = idx.reshape(ng, tl * k)
        gtok = jnp.broadcast_to(jnp.repeat(jnp.arange(tl), k)[None], (ng, tl * k))
        gw = w.reshape(ng, tl * k)
        ge = maybe_wsc(ge, dp, None)
        order = jnp.argsort(ge, axis=-1, stable=True)
        se = jnp.take_along_axis(ge, order, axis=-1)
        stok = jnp.take_along_axis(gtok, order, axis=-1)
        sw = jnp.take_along_axis(gw, order, axis=-1)

        tok_buf, w_buf, keep = jax.vmap(
            lambda a_, b_, c_: _dispatch_indices(a_, b_, c_, e, cap))(se, stok, sw)
        tok_buf = tok_buf.reshape(ng, e, cap)
        w_buf = w_buf.reshape(ng, e, cap)

        # Gather activations at (G, E, C, d): shard G over dp, E over model.
        # Every activation-side tensor is pinned: with FSDP param sharding the
        # contracting dim also wants 'data', and without pins GSPMD resolves
        # the conflict by UNsharding the group dim (measured: 5 GiB f32 expert
        # intermediates per instance on deepseek prefill).
        xg = maybe_wsc(xt.reshape(ng, tl, d), dp, None, None)
        xg_pad = jnp.concatenate([xg, jnp.zeros((ng, 1, d), x.dtype)], axis=1)
        buf = jax.vmap(lambda xp, tb: xp[tb])(xg_pad, tok_buf)  # (G, E, C, d)
        buf = maybe_wsc(buf, dp, "model", None, None)

    with jax.named_scope("experts"):
        # Expert SwiGLU (EP over 'model'; G rides along sharded over dp).
        ew = params["experts"]
        # repro: allow-raw-param-matmul (grouped per-expert einsum: the (E,d,f)
        # weight has no 2-D rhs form tsmm accepts, and the contraction must
        # stay a single GSPMD op so EP resolves to all-to-alls)
        g = maybe_wsc(jnp.einsum("gecd,edf->gecf", buf, ew["w_gate"],
                                 preferred_element_type=jnp.float32),
                      dp, "model", None, None)
        # repro: allow-raw-param-matmul (same grouped-expert form as w_gate)
        u = maybe_wsc(jnp.einsum("gecd,edf->gecf", buf, ew["w_up"],
                                 preferred_element_type=jnp.float32),
                      dp, "model", None, None)
        h = (jax.nn.silu(g) * u).astype(x.dtype)
        h = maybe_wsc(h, dp, "model", None, None)
        # repro: allow-raw-param-matmul (same grouped-expert form as w_gate)
        y = jnp.einsum("gecf,efd->gecd", h, ew["w_down"],
                       preferred_element_type=jnp.float32).astype(x.dtype)
        y = maybe_wsc(y, dp, "model", None, None)

    with jax.named_scope("dispatch"):
        # Combine: weighted scatter-add back to tokens (index-addressed).
        yw = y * w_buf[..., None].astype(x.dtype)

        def combine(yg, tb):
            out = jnp.zeros((tl + 1, d), x.dtype)
            return out.at[tb.reshape(-1)].add(yg.reshape(-1, d))[:tl]

        out = jax.vmap(combine)(yw, tok_buf)               # (G, tl, d)
        out = maybe_wsc(out, dp, None, None).reshape(t, d)
    if cfg.n_shared:
        out = out + layers.swiglu(params["shared"], xt)

    counts = (w_buf > 0).sum(axis=(0, 2))              # honored slots per E
    frac_tokens = counts / jnp.maximum(counts.sum(), 1)
    metrics = {
        "moe_balance_loss": e * jnp.sum(frac_tokens * probs.mean(axis=0)),
        "moe_max_load": frac_tokens.max() * e,
        "moe_dropped_frac": 1.0 - keep.mean(),
    }
    return out.reshape(b, s, d), metrics


def update_router_bias(params, metrics_counts, rate: float = 1e-3):
    """DeepSeek aux-loss-free balancing: nudge under-loaded experts up."""
    counts = metrics_counts
    target = counts.mean()
    delta = jnp.sign(target - counts) * rate
    return {**params, "router_bias": params["router_bias"] + delta}
