"""GSPMD partition rules: param-path regex -> PartitionSpec.

Axis roles:
* ``dp``   -- batch data parallelism: ('pod','data') on the multi-pod mesh,
  ('data',) on a single pod.
* ``model``-- tensor/expert parallelism.
* FSDP    -- for huge archs (param_count > FSDP_THRESHOLD) weight matrices
  additionally shard their *input* dim over 'data' (ZeRO-3-style); the
  optimizer moments inherit param specs, so ZeRO-1 comes for free.

All rules are divisibility-guarded: a dim that doesn't divide its mesh axis
falls back to replication (e.g. hubert's vocab=504 on model=16). Specs are
right-aligned: rules describe the trailing dims; leading scan/stack axes
(layers, groups) are padded with None.

KV-cache layout: kv-head counts (8) are below the model-axis size (16), so
decode caches shard their *sequence* dim over 'model' -- sequence
parallelism for long-context decode; GSPMD turns the masked softmax over
the sharded axis into the two-pass collective combine
(distributed/collectives.py holds the explicit shard_map variant used for
§Perf comparison).
"""

from __future__ import annotations

import re

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import tsmm
from repro.kernels import compat

FSDP_THRESHOLD = 30e9


def abstract_mesh(axis_sizes, axis_names):
    """Device-free mesh for spec logic; AbstractMesh signature drifted
    across JAX versions, so construction goes through the compat layer."""
    return compat.abstract_mesh(axis_sizes, axis_names)


def dp_axes(mesh: Mesh):
    """Data-parallel axes of ``mesh``. Shares one derivation with the GEMM
    dispatcher (``tsmm.derive_dp_axes``): conventional names
    ('pod'/'data'/'dp'/'batch'/'replica') when present, otherwise any
    non-model-named axis; a single-axis mesh is always DP. Batch specs,
    PowerSGD reductions, and the shard_map executors therefore agree on
    which axes carry the batch without the ("pod", "data") names being
    hard-coded anywhere."""
    return tsmm.derive_dp_axes(mesh)


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        n = 1
        for a in axis:
            n *= mesh.shape[a]
        return n
    return mesh.shape[axis]


def _guard(mesh: Mesh, spec: P, shape) -> P:
    """Replicate any dim that doesn't divide its assigned axis."""
    out = []
    offset = len(shape) - len(spec)
    padded = (None,) * offset + tuple(spec)
    for dim, axis in zip(shape, padded):
        if axis is not None and dim % _axis_size(mesh, axis) == 0:
            out.append(axis)
        else:
            out.append(None)
    return P(*out)


def param_rules(cfg, mesh: Mesh, fsdp: bool | None = None):
    """Ordered (regex, trailing-dims PartitionSpec) rules."""
    if fsdp is None:
        fsdp = cfg.param_count() > FSDP_THRESHOLD
    d = "data" if (fsdp and "data" in mesh.axis_names) else None
    return [
        # embeddings / heads
        (r"(embed|lm_head)/table$", P("model", d)),
        (r"frame_proj/w$", P(None, "model")),
        # attention projections
        (r"attn/wq$", P(d, "model")),
        (r"attn/wk$", P(d, "model")),
        (r"attn/wv$", P(d, "model")),
        (r"attn/wo$", P("model", d)),
        (r"attn/b[qkv]$", P("model")),
        # MLA
        (r"attn/wdq$", P(d, None)),
        (r"attn/wuq$", P(None, "model")),
        (r"attn/wdkv$", P(d, None)),
        (r"attn/wukv$", P(None, "model")),
        (r"attn/wkr$", P(d, None)),
        # cross-attn image projections
        (r"kv_proj_[kv]$", P(None, "model")),
        # MoE routed experts: expert dim over model (EP). Mixtral's E=8 < 16
        # fails the divisibility guard on 'model' and falls through to
        # TP-within-expert via the d_ff dim (second rule set).
        (r"experts/w_gate$", P("model", d, None)),
        (r"experts/w_up$", P("model", d, None)),
        (r"experts/w_down$", P("model", None, d)),
        (r"router_w$", P(None, None)),
        # dense MLPs (swiglu / gelu) incl. MoE shared expert
        (r"(ffn|shared)/w_gate$", P(d, "model")),
        (r"(ffn|shared)/w_up$", P(d, "model")),
        (r"(ffn|shared)/w_down$", P("model", d)),
        (r"ffn/b_up$", P("model")),
        # Mamba2
        (r"mixer/in_proj$", P(d, "model")),
        (r"mixer/out_proj$", P("model", d)),
        (r"mixer/conv_w$", P(None, "model")),
        (r"mixer/conv_b$", P("model")),
        # RWKV6
        (r"time_mix/w[rkvg]$", P(d, "model")),
        (r"time_mix/wo$", P("model", d)),
        (r"channel_mix/wk$", P(d, "model")),
        (r"channel_mix/wv$", P("model", d)),
        (r"channel_mix/wr$", P(d, None)),
        # default: replicate (norms, biases, gates, LoRAs, scalars)
        (r".*", P()),
    ]


def path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def make_param_specs(cfg, params_shape, mesh: Mesh, fsdp: bool | None = None,
                     strategy: str = "tp"):
    """params_shape: pytree of ShapeDtypeStruct (or arrays). Returns specs.

    strategy='tp'  -- tensor/expert parallelism over 'model' (+FSDP for
                      huge archs): the framework default.
    strategy='dp'  -- replicate params; batch shards over EVERY mesh axis
                      and the optimizer state is ZeRO-1 sharded over the
                      whole mesh. Right for small archs where 16-way TP
                      pays ~2 all-reduces/layer for no memory need
                      (§Perf hillclimb).
    """
    if strategy == "dp":
        return jax.tree.map(lambda _: P(), params_shape)
    rules = param_rules(cfg, mesh, fsdp)

    def assign(path, leaf):
        ps = path_str(path)
        for pat, spec in rules:
            if re.search(pat, ps):
                # Mixtral fallback: EP spec replicated by the guard on E=8
                # => TP-within-expert on d_ff instead.
                g = _guard(mesh, spec, leaf.shape)
                if (re.search(r"experts/w_(gate|up)$", ps)
                        and g[len(leaf.shape) - 3] is None):
                    g = _guard(mesh, P(None, None, "model"), leaf.shape)
                if (re.search(r"experts/w_down$", ps)
                        and g[len(leaf.shape) - 3] is None):
                    g = _guard(mesh, P(None, "model", None), leaf.shape)
                return g
        return P()

    return jax.tree_util.tree_map_with_path(assign, params_shape)


def make_opt_specs(param_specs, *, mesh: Mesh | None = None,
                   params_shape=None, zero1: bool = False):
    """Optimizer state mirrors params; step counter replicated.

    ``zero1=True``: moments additionally shard their largest divisible dim
    over the WHOLE mesh (ZeRO-1) -- used with strategy='dp' where params
    are replicated but 8 bytes/param of moments must not be.
    """
    if not zero1:
        return {
            "step": P(),
            "moments": jax.tree.map(lambda s: {"m": s, "v": s}, param_specs,
                                    is_leaf=lambda x: isinstance(x, P)),
        }
    assert mesh is not None and params_shape is not None
    all_axes = tuple(mesh.axis_names)
    world = mesh.size

    def one(spec, shp):
        dims = list(shp.shape)
        order = sorted(range(len(dims)), key=lambda i: -dims[i])
        for i in order:
            if dims[i] % world == 0:
                out = [None] * len(dims)
                out[i] = all_axes
                return {"m": P(*out), "v": P(*out)}
        return {"m": spec, "v": spec}

    return {
        "step": P(),
        "moments": jax.tree.map(one, param_specs, params_shape,
                                is_leaf=lambda x: isinstance(x, P)),
    }


def batch_specs(cfg, mesh: Mesh, batch_shape, strategy: str = "tp"):
    """Input batch: shard leading batch dim over dp (guarded); under
    strategy='dp' the batch shards over every mesh axis."""
    dp = tuple(mesh.axis_names) if strategy == "dp" else dp_axes(mesh)

    def assign(_, leaf):
        spec = [None] * len(leaf.shape)
        if len(leaf.shape) >= 1 and leaf.shape[0] % _axis_size(mesh, dp) == 0:
            spec[0] = dp
        return P(*spec)

    return jax.tree_util.tree_map_with_path(assign, batch_shape)


def cache_specs(cfg, mesh: Mesh, cache_shape):
    """KV caches: batch over dp; heads over model if divisible else
    sequence over model (SP); SSM states: heads over model."""
    dp = dp_axes(mesh)
    dp_n = _axis_size(mesh, dp)
    tp_n = _axis_size(mesh, "model")

    def assign(path, leaf):
        ps = path_str(path)
        shape = leaf.shape
        name = ps.rsplit("/", 1)[-1]
        spec = [None] * len(shape)
        # find the batch dim: first dim matching known layouts
        if name in ("k", "v"):           # (..., B, S, Hk, Hd)
            b_ax = len(shape) - 4
            if shape[b_ax] % dp_n == 0:
                spec[b_ax] = dp
            if shape[-2] % tp_n == 0:
                spec[-2] = "model"
            elif shape[-3] % tp_n == 0:
                spec[-3] = "model"       # sequence-parallel cache
        elif name in ("c", "kpe"):       # MLA latent: (..., B, S, D)
            b_ax = len(shape) - 3
            if shape[b_ax] % dp_n == 0:
                spec[b_ax] = dp
            if shape[-2] % tp_n == 0:
                spec[-2] = "model"       # sequence-parallel latent cache
        elif name == "ssm":              # (..., B, H, N, P)
            b_ax = len(shape) - 4
            if shape[b_ax] % dp_n == 0:
                spec[b_ax] = dp
            if shape[-3] % tp_n == 0:
                spec[-3] = "model"
        elif name == "wkv":              # (..., B, H, D, D)
            b_ax = len(shape) - 4
            if shape[b_ax] % dp_n == 0:
                spec[b_ax] = dp
            if shape[-3] % tp_n == 0:
                spec[-3] = "model"
        elif name == "conv":             # (..., B, W-1, C)
            b_ax = len(shape) - 3
            if shape[b_ax] % dp_n == 0:
                spec[b_ax] = dp
            if shape[-1] % tp_n == 0:
                spec[-1] = "model"
        elif name in ("tm_prev", "cm_prev"):  # (..., B, 1, d)
            b_ax = len(shape) - 3
            if shape[b_ax] % dp_n == 0:
                spec[b_ax] = dp
        return P(*spec)

    return jax.tree_util.tree_map_with_path(assign, cache_shape)


def _context_mesh():
    """The ``jax.set_mesh`` context mesh (abstract), or None outside one."""
    return compat.get_context_mesh()


def maybe_wsc_spec(x, spec):
    """maybe_wsc with an explicit PartitionSpec."""
    return maybe_wsc(x, *tuple(spec))


def maybe_wsc(x, *spec):
    """with_sharding_constraint that (a) degrades to identity outside a
    mesh context (smoke tests / single-device runs), and (b) drops axis
    names the current mesh doesn't have (e.g. 'pod' on a single pod) and
    dims that don't divide their axis."""
    mesh = _context_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)

    def filt(entry, dim):
        if entry is None:
            return None
        if isinstance(entry, tuple):
            kept = tuple(a for a in entry if a in names)
            entry = kept if kept else None
        elif entry not in names:
            entry = None
        if entry is not None and dim % _axis_size(mesh, entry) != 0:
            entry = None
        return entry

    full = list(spec) + [None] * (x.ndim - len(spec))
    try:
        return jax.lax.with_sharding_constraint(
            x, P(*(filt(s, d) for s, d in zip(full, x.shape))))
    except Exception:
        return x


def named(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
