"""LM assembly: stacks blocks into the ten assigned architectures.

Layer stacks are ``lax.scan`` over parameter pytrees stacked on a leading
layer axis -- compile time is O(1) in depth (an 80-layer qwen2-72b lowers
as fast as a 2-layer smoke model), and remat wraps the scan body.

Heterogeneous architectures are expressed as *segments*, each a homogeneous
scan:

* dense/audio:   [attn_mlp x L]
* mixtral:       [attn_moe x L]
* deepseek-v3:   [mla_mlp x 3, mla_moe x (L-3)]
* rwkv6:         [rwkv x L]
* zamba2:        [zamba_group x G] + [mamba x rem] -- each group = `period`
                 Mamba2 layers (inner scan) + the weight-SHARED attention
                 block with a per-group LoRA (scan carries only the LoRA).
* llama3.2-vision: [vlm_group x 8] -- each group = 4 self layers (inner
                 scan) + 1 gated cross-attention layer.

Profile scopes (``jax.named_scope``; components of every HLO ``op_name``,
so a device profile names the model's layers): ``embed``; ``layers`` around
each segment's scan, where an op outside any ``block`` or ``shared_block``
is the scan's own work -- slicing the stacked weights and caches per layer
and stacking the per-layer outputs; ``block`` per layer and
``shared_block`` per application of zamba2's shared block, each with their
sub-blocks' scopes beneath (``time_mix``/``wkv``, ``channel_mix``,
``mamba``/``ssd``, ``attn``/``attn_core``, ``mlp``, ``lora``, and ``dense``
with ``tsmm.<kind>`` for every projection); ``unembed``, the final norm and
vocab projection.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import attention, blocks, layers


@dataclasses.dataclass(frozen=True)
class Segment:
    kind: str          # block kind | zamba_group | vlm_group
    n: int             # outer scan length
    inner: int = 0     # inner layers per group


def segments(cfg) -> list[Segment]:
    f = cfg.family
    if f in ("dense", "audio"):
        return [Segment("attn_mlp", cfg.n_layers)]
    if f == "moe":
        if cfg.mla is not None:
            return [Segment("mla_mlp", cfg.first_k_dense),
                    Segment("mla_moe", cfg.n_layers - cfg.first_k_dense)]
        return [Segment("attn_moe", cfg.n_layers)]
    if f == "ssm":
        return [Segment("rwkv", cfg.n_layers)]
    if f == "hybrid":
        g = cfg.n_layers // cfg.hybrid_period
        rem = cfg.n_layers - g * cfg.hybrid_period
        segs = [Segment("zamba_group", g, inner=cfg.hybrid_period)]
        if rem:
            segs.append(Segment("mamba", rem))
        return segs
    if f == "vlm":
        period = cfg.cross_attn_period
        g = cfg.n_layers // period
        return [Segment("vlm_group", g, inner=period - 1)]
    raise ValueError(f)


def _stack_init(key, n: int, init_one):
    return jax.vmap(init_one)(jax.random.split(key, n))


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init(key, cfg):
    dt = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 8)
    params = {}
    if cfg.input_mode == "frames":
        params["frame_proj"] = {
            "w": layers.dense_init(keys[0], cfg.frame_dim, cfg.d_model, dt)}
        params["embed"] = layers.embedding_init(keys[1], cfg.vocab_size,
                                                cfg.d_model, dt)  # unembed table
    else:
        params["embed"] = layers.embedding_init(keys[1], cfg.vocab_size,
                                                cfg.d_model, dt)
    seg_params = []
    for i, seg in enumerate(segments(cfg)):
        k = jax.random.fold_in(keys[2], i)
        if seg.kind == "zamba_group":
            seg_params.append({
                "mamba": _stack_init(
                    k, seg.n,
                    lambda kk: _stack_init(kk, seg.inner,
                                           lambda k2: blocks.block_init(k2, cfg, "mamba"))),
                "lora_attn": _stack_init(
                    jax.random.fold_in(k, 1), seg.n,
                    lambda kk: layers.lora_init(kk, cfg.d_model, cfg.d_model,
                                                cfg.shared_lora_rank, dt)),
                "lora_ffn": _stack_init(
                    jax.random.fold_in(k, 2), seg.n,
                    lambda kk: layers.lora_init(kk, cfg.d_model, cfg.d_model,
                                                cfg.shared_lora_rank, dt)),
            })
        elif seg.kind == "vlm_group":
            seg_params.append({
                "self": _stack_init(
                    k, seg.n,
                    lambda kk: _stack_init(kk, seg.inner,
                                           lambda k2: blocks.block_init(k2, cfg, "attn_mlp"))),
                "cross": _stack_init(
                    jax.random.fold_in(k, 1), seg.n,
                    lambda kk: blocks.block_init(kk, cfg, "cross_mlp")),
            })
        else:
            seg_params.append(_stack_init(
                k, seg.n, lambda kk, kind=seg.kind: blocks.block_init(kk, cfg, kind)))
    params["segments"] = seg_params
    if cfg.family == "hybrid":
        params["shared_block"] = blocks.block_init(keys[3], cfg, "attn_mlp")
    params["final_norm"] = blocks._norm_init(cfg)
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.embedding_init(keys[4], cfg.vocab_size,
                                                  cfg.d_model, dt)
    return params


# ---------------------------------------------------------------------------
# Shared helpers
# ---------------------------------------------------------------------------

@layers.scoped("embed")
def _embed_input(params, cfg, batch):
    if cfg.input_mode == "frames":
        return layers.dense(params["frame_proj"]["w"], batch["frames"])
    return layers.embed(params["embed"], batch["tokens"])


@layers.scoped("unembed")
def _logits(params, cfg, x):
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    return layers.unembed(head, blocks.norm_apply(cfg, params["final_norm"], x))


@layers.scoped("shared_block")
def _shared_block_fwd(shared_p, lora_a, lora_f, x, cfg, mode, cache=None, pos=None):
    """Zamba2's weight-shared attention block + per-application LoRA
    (profile scope ``shared_block``)."""
    n1 = blocks.norm_apply(cfg, shared_p["norm1"], x)
    kw = blocks._attn_kwargs(cfg)
    if mode == "train":
        h, _ = attention.gqa_fwd(shared_p["attn"], n1, causal=cfg.causal,
                                 q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk, **kw)
    elif mode == "prefill":
        h, (k, v) = attention.gqa_fwd(shared_p["attn"], n1, causal=cfg.causal,
                                      q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk, **kw)
        cache = {"k": lax.dynamic_update_slice_in_dim(cache["k"], k, 0, 1),
                 "v": lax.dynamic_update_slice_in_dim(cache["v"], v, 0, 1)}
    else:
        h, ck, cv = attention.gqa_decode(shared_p["attn"], n1, cache["k"],
                                         cache["v"], pos, **kw)
        cache = {"k": ck, "v": cv}
    h = h + layers.lora_apply(lora_a, n1)
    x = x + h
    n2 = blocks.norm_apply(cfg, shared_p["norm2"], x)
    with jax.named_scope("mlp"):
        h2 = layers.swiglu(shared_p["ffn"], n2)
    h2 = h2 + layers.lora_apply(lora_f, n2)
    return x + h2, cache


def _zero_metrics(kind):
    if kind in ("attn_moe", "mla_moe"):
        return None  # block produces real metrics
    return {}


def _maybe_remat(cfg, fn):
    return jax.checkpoint(fn) if cfg.remat else fn


def _remat_group_size(cfg, n: int) -> int:
    """Largest divisor of n that is <= cfg.remat_group."""
    g = max(1, min(cfg.remat_group, n))
    while n % g:
        g -= 1
    return g


def _scan_layers_remat(cfg, seg_p, x, kind, n: int):
    """Homogeneous layer scan with nested-scan remat: outer scan saves only
    n/g residuals; the inner g-layer scan recomputes in the backward.

    For a 28L model at (16-seq, 4k, d) bf16 activations this turns an 11 GB
    carry-save into 2.8 GB (g=4) -- the measured difference in the dry-run
    iteration log."""
    def inner_body(h, lp):
        # Barrier keeps the f32 upcast of the residual loop-local: without
        # it XLA hoists convert(saved_stack) out of the backward while-loop,
        # materializing an f32 copy of ALL layer saves at once (21 GiB for
        # llama3.2-3b train_4k -- measured via buffer assignment).
        h = lax.optimization_barrier(h)
        out, met = blocks.block_fwd(lp, h, cfg, kind)
        return out, met

    g = _remat_group_size(cfg, n) if cfg.remat else 1
    if g <= 1:
        body = _maybe_remat(cfg, inner_body)
        return lax.scan(body, x, seg_p)

    grouped = jax.tree.map(lambda a: a.reshape(n // g, g, *a.shape[1:]), seg_p)

    def outer_body(h, gp):
        return lax.scan(inner_body, h, gp)

    x, mets = lax.scan(jax.checkpoint(outer_body), x, grouped)
    mets = jax.tree.map(lambda m: m.reshape(n, *m.shape[2:]), mets)
    return x, mets


# ---------------------------------------------------------------------------
# Training forward
# ---------------------------------------------------------------------------

def forward(params, cfg, batch):
    """Returns (logits f32 (B,S,V), metrics)."""
    x, metrics = forward_hidden(params, cfg, batch)
    return _logits(params, cfg, x), metrics


def unembed_fn(params, cfg):
    """Closure for sequence-chunked loss: x_chunk -> logits_chunk."""
    return lambda xc: _logits(params, cfg, xc)


def forward_hidden(params, cfg, batch):
    """Backbone only: returns (hidden (B,S,d), metrics) -- the training
    path computes the head inside losses.chunked_lm_loss to bound logits
    memory."""
    x = _embed_input(params, cfg, batch)
    extras = {"image_embeds": batch.get("image_embeds")} if cfg.family == "vlm" else None
    all_metrics = []

    for seg, seg_p in zip(segments(cfg), params["segments"]):
        if seg.kind == "zamba_group":
            shared = params["shared_block"]

            def group_body(h, xs, shared=shared):
                gp = xs

                def mamba_body(hh, lp):
                    out, _ = blocks.block_fwd(lp, hh, cfg, "mamba")
                    return out, None

                h, _ = lax.scan(_maybe_remat(cfg, mamba_body), h, gp["mamba"])
                h, _ = _shared_block_fwd(shared, gp["lora_attn"], gp["lora_ffn"],
                                         h, cfg, "train")
                return h, None

            with jax.named_scope("layers"):
                x, _ = lax.scan(group_body, x, seg_p)
        elif seg.kind == "vlm_group":
            def vgroup_body(h, xs):
                def self_body(hh, lp):
                    out, _ = blocks.block_fwd(lp, hh, cfg, "attn_mlp")
                    return out, None

                h, _ = lax.scan(_maybe_remat(cfg, self_body), h, xs["self"])
                h, _ = blocks.block_fwd(xs["cross"], h, cfg, "cross_mlp", extras)
                return h, None

            with jax.named_scope("layers"):
                x, _ = lax.scan(_maybe_remat(cfg, vgroup_body), x, seg_p)
        else:
            with jax.named_scope("layers"):
                x, mets = _scan_layers_remat(cfg, seg_p, x, seg.kind, seg.n)
            if mets:
                all_metrics.append(jax.tree.map(jnp.sum, mets))

    metrics = {}
    for m in all_metrics:
        for k, v in m.items():
            metrics[k] = metrics.get(k, 0.0) + v
    return x, metrics


# ---------------------------------------------------------------------------
# Cache init / prefill / decode
# ---------------------------------------------------------------------------

def init_cache(cfg, batch_size: int, max_len: int):
    caches = []
    for seg in segments(cfg):
        if seg.kind == "zamba_group":
            mamba = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (seg.n, seg.inner) + x.shape),
                blocks.cache_init(cfg, "mamba", batch_size, max_len))
            shared = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (seg.n,) + x.shape),
                blocks.cache_init(cfg, "attn_mlp", batch_size, max_len))
            caches.append({"mamba": mamba, "shared": shared})
        elif seg.kind == "vlm_group":
            selfc = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (seg.n, seg.inner) + x.shape),
                blocks.cache_init(cfg, "attn_mlp", batch_size, max_len))
            crossc = jax.tree.map(
                lambda x: jnp.broadcast_to(x, (seg.n,) + x.shape),
                blocks.cache_init(cfg, "cross_mlp", batch_size, max_len))
            caches.append({"self": selfc, "cross": crossc})
        else:
            caches.append(jax.tree.map(
                lambda x: jnp.broadcast_to(x, (seg.n,) + x.shape),
                blocks.cache_init(cfg, seg.kind, batch_size, max_len)))
    return caches


def prefill(params, cfg, batch, cache):
    """Returns (last-token logits (B,V), cache)."""
    x = _embed_input(params, cfg, batch)
    extras = {"image_embeds": batch.get("image_embeds")} if cfg.family == "vlm" else None
    new_caches = []

    for seg, seg_p, seg_c in zip(segments(cfg), params["segments"], cache):
        if seg.kind == "zamba_group":
            shared = params["shared_block"]

            def group_body(h, xs, shared=shared):
                gp, gc = xs

                def mamba_body(hh, inner):
                    lp, lc = inner
                    out, nc = blocks.block_prefill(lp, hh, cfg, "mamba", lc)
                    return out, nc

                h, mamba_c = lax.scan(mamba_body, h, (gp["mamba"], gc["mamba"]))
                h, shared_c = _shared_block_fwd(
                    shared, gp["lora_attn"], gp["lora_ffn"], h, cfg, "prefill",
                    cache=gc["shared"])
                return h, {"mamba": mamba_c, "shared": shared_c}

            with jax.named_scope("layers"):
                x, nc = lax.scan(group_body, x, (seg_p, seg_c))
        elif seg.kind == "vlm_group":
            def vgroup_body(h, xs):
                gp, gc = xs

                def self_body(hh, inner):
                    lp, lc = inner
                    out, nc2 = blocks.block_prefill(lp, hh, cfg, "attn_mlp", lc)
                    return out, nc2

                h, self_c = lax.scan(self_body, h, (gp["self"], gc["self"]))
                h, cross_c = blocks.block_prefill(gp["cross"], h, cfg,
                                                  "cross_mlp", gc["cross"], extras)
                return h, {"self": self_c, "cross": cross_c}

            with jax.named_scope("layers"):
                x, nc = lax.scan(vgroup_body, x, (seg_p, seg_c))
        else:
            def body(h, xs, kind=seg.kind):
                lp, lc = xs
                out, nc2 = blocks.block_prefill(lp, h, cfg, kind, lc)
                return out, nc2

            with jax.named_scope("layers"):
                x, nc = lax.scan(body, x, (seg_p, seg_c))
        new_caches.append(nc)

    logits = _logits(params, cfg, x[:, -1:])[:, 0]
    return logits, new_caches


def decode_step(params, cfg, tokens, pos, cache):
    """tokens: (B, 1) int32; pos: scalar int32. Returns (logits (B,V), cache)."""
    with jax.named_scope("embed"):
        x = layers.embed(params["embed"], tokens)
    new_caches = []

    for seg, seg_p, seg_c in zip(segments(cfg), params["segments"], cache):
        if seg.kind == "zamba_group":
            shared = params["shared_block"]

            def group_body(h, xs, shared=shared):
                gp, gc = xs

                def mamba_body(hh, inner):
                    lp, lc = inner
                    out, nc = blocks.block_decode(lp, hh, cfg, "mamba", lc, pos)
                    return out, nc

                h, mamba_c = lax.scan(mamba_body, h, (gp["mamba"], gc["mamba"]))
                h, shared_c = _shared_block_fwd(
                    shared, gp["lora_attn"], gp["lora_ffn"], h, cfg, "decode",
                    cache=gc["shared"], pos=pos)
                return h, {"mamba": mamba_c, "shared": shared_c}

            with jax.named_scope("layers"):
                x, nc = lax.scan(group_body, x, (seg_p, seg_c))
        elif seg.kind == "vlm_group":
            def vgroup_body(h, xs):
                gp, gc = xs

                def self_body(hh, inner):
                    lp, lc = inner
                    out, nc2 = blocks.block_decode(lp, hh, cfg, "attn_mlp", lc, pos)
                    return out, nc2

                h, self_c = lax.scan(self_body, h, (gp["self"], gc["self"]))
                h, cross_c = blocks.block_decode(gp["cross"], h, cfg,
                                                 "cross_mlp", gc["cross"], pos)
                return h, {"self": self_c, "cross": cross_c}

            with jax.named_scope("layers"):
                x, nc = lax.scan(vgroup_body, x, (seg_p, seg_c))
        else:
            def body(h, xs, kind=seg.kind):
                lp, lc = xs
                out, nc2 = blocks.block_decode(lp, h, cfg, kind, lc, pos)
                return out, nc2

            with jax.named_scope("layers"):
                x, nc = lax.scan(body, x, (seg_p, seg_c))
        new_caches.append(nc)

    logits = _logits(params, cfg, x)[:, 0]
    return logits, new_caches
