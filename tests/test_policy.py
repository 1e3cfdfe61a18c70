"""GemmPolicy surface: scoping, validation, thresholds, batched N-d
entries, the backend registry, and the dispatch spy.

Everything runs single-device (interpret mode); the >1-device shard_map
executor is covered by tests/test_shard_map.py in a subprocess with
``--xla_force_host_platform_device_count``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import perf_model, tsmm
from repro.kernels import compat, ref

TOL = dict(rtol=1e-3, atol=1e-3)


def _rand(seed, shape):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)


# ---------------------------------------------------------------------------
# Policy object + scoping
# ---------------------------------------------------------------------------

def test_policy_defaults():
    p = tsmm.GemmPolicy()
    assert p.mode == "auto" and p.spec is perf_model.V5E
    assert (p.skinny_ratio, p.max_skinny, p.min_tall) == (16, 256, 2048)
    assert (p.max_skinny_t, p.skinny_ratio_t) == (512, 4)


def test_policy_mode_validation_at_construction():
    with pytest.raises(ValueError, match="valid modes"):
        tsmm.GemmPolicy(mode="tsmr")
    with pytest.raises(ValueError, match="valid values"):
        tsmm.GemmPolicy(shard_map="sometimes")


def test_unknown_force_kind_raises():
    a, b = _rand(0, (64, 8)), _rand(1, (8, 4))
    with pytest.raises(ValueError, match="valid kinds are auto, dense, tsm2r, tsm2l"):
        tsmm.tsmm(a, b, mode="tsmr")
    with pytest.raises(ValueError, match="valid kinds are auto, dense, tsm2r, tsm2l"):
        tsmm.tsmm(a, b, force="tsmt")          # deprecated alias validates too
    x, y = _rand(2, (64, 8)), _rand(3, (64, 4))
    with pytest.raises(ValueError, match="valid kinds are auto, dense, tsmt"):
        tsmm.tsmm_t(x, y, mode="tsm2r")


def test_policy_nesting_and_restoration():
    base = tsmm.current_policy()
    with tsmm.policy(mode="dense") as p1:
        assert tsmm.current_policy() is p1
        with tsmm.policy(interpret=True) as p2:
            # inner scope derives from the outer one
            assert p2.mode == "dense" and p2.interpret is True
        assert tsmm.current_policy() is p1
    assert tsmm.current_policy() is base


def test_policy_restored_across_exceptions():
    with pytest.raises(RuntimeError):
        with tsmm.policy(mode="dense"):
            raise RuntimeError("boom")
    assert tsmm.current_policy().mode == "auto"


def test_policy_explicit_base():
    pinned = tsmm.GemmPolicy(mode="dense", interpret=True)
    with tsmm.policy(pinned) as p:
        assert p is pinned
    with tsmm.policy(pinned, mode="auto") as p:
        assert p.mode == "auto" and p.interpret is True


def test_trace_time_capture_under_jit():
    """A jitted caller bakes the scoped policy into its cache entry."""
    a, b = _rand(2, (4096, 16)), _rand(3, (16, 8))
    f = jax.jit(lambda a_, b_: tsmm.tsmm(a_, b_))
    with tsmm.policy(mode="dense"):
        with tsmm.record_dispatches() as log:
            f(a, b)
        assert [e.executor for e in log] == ["dense-xla"]
    # Cached call outside the scope: no re-trace, no new dispatch decision.
    with tsmm.record_dispatches() as log:
        f(a, b)
    assert log == []
    # A fresh jit outside the scope classifies and hits the kernel path.
    g = jax.jit(lambda a_, b_: tsmm.tsmm(a_, b_))
    with tsmm.record_dispatches() as log:
        g(a, b)
    assert [(e.kind, e.executor) for e in log] == [("tsm2l", "pallas-tpu")]


# ---------------------------------------------------------------------------
# Classifier thresholds as policy fields
# ---------------------------------------------------------------------------

def test_classify_gemm_boundaries():
    p = tsmm.GemmPolicy()
    assert tsmm.classify_gemm(2048, 16, 8, p) == "tsm2l"     # at min_tall
    assert tsmm.classify_gemm(2047, 16, 8, p) == "dense"     # below it
    assert tsmm.classify_gemm(8192, 256, 8, p) == "tsm2l"    # at max_skinny k
    assert tsmm.classify_gemm(8192, 257, 8, p) == "tsm2r"    # past it, k>=16n
    assert tsmm.classify_gemm(2048, 2048, 256, p) == "dense"   # m < 16n
    assert tsmm.classify_gemm(4096, 4096, 256, p) == "tsm2r"   # m == 16n
    assert tsmm.classify_gemm(4096, 4096, 257, p) == "dense"   # n past bound


def test_classify_gemm_t_boundaries():
    """Pin the transposed-entry boundary the named fields own: b <= 512
    (t2_threshold ~ 481 rounded up to the lane multiple) and m >= 4*max."""
    p = tsmm.GemmPolicy()
    assert tsmm.classify_gemm_t(2048, 128, 512, p) == "tsmt"   # both at bound
    assert tsmm.classify_gemm_t(2048, 128, 513, p) == "dense"  # b past bound
    assert tsmm.classify_gemm_t(2047, 128, 512, p) == "dense"  # below min_tall
    assert tsmm.classify_gemm_t(2048, 513, 512, p) == "dense"  # m < 4*513
    assert tsmm.classify_gemm_t(4 * 513, 513, 8, p) == "tsmt"  # m == 4*max
    assert tsmm.classify_gemm_t(4 * 513 - 1, 513, 8, p) == "dense"


def test_classify_matches_legacy_constants():
    """The field defaults reproduce the legacy module-global behavior
    (16*max//4 == 4*max exactly)."""
    for m, a_dim, b_dim in [(4096, 32, 8), (2048, 128, 512), (100000, 300, 16),
                            (512, 512, 1), (8192, 2048, 8)]:
        legacy = ("tsmt" if (m >= 2048 and b_dim <= 512
                             and m >= 16 * max(a_dim, b_dim) // 4)
                  else "dense")
        assert tsmm.classify_gemm_t(m, a_dim, b_dim) == legacy


def test_threshold_overrides_change_routing():
    with tsmm.policy(min_tall=64):
        assert tsmm.classify_gemm(128, 128, 2) == "tsm2l"   # k <= max_skinny
        assert tsmm.classify_gemm(128, 512, 2) == "tsm2r"
    assert tsmm.classify_gemm(128, 128, 2) == "dense"
    with tsmm.policy(max_skinny_t=8):
        assert tsmm.classify_gemm_t(4096, 32, 16) == "dense"
    assert tsmm.classify_gemm_t(4096, 32, 16) == "tsmt"


def test_spec_field_drives_perf_model():
    # n ~ 200 sits between the two generations' flops/byte ridges
    # (v5e ~ 241, v5p ~ 166): the same shape flips bound class with spec.
    assert tsmm.bound_class(20480, 20480, 200) == "memory"
    with tsmm.policy(spec=perf_model.V5P):
        assert tsmm.bound_class(20480, 20480, 200) == "compute"
    assert perf_model.get_spec("v5p") is perf_model.V5P
    with pytest.raises(ValueError, match="unknown TPU spec"):
        perf_model.get_spec("v6z")


# ---------------------------------------------------------------------------
# Batched N-d entries
# ---------------------------------------------------------------------------

def test_batched_tsmm_matches_oracle():
    a = _rand(4, (4, 1024, 16))        # collapses to (4096, 16) -> tsm2l
    b = _rand(5, (16, 8))
    with tsmm.record_dispatches() as log:
        got = tsmm.tsmm(a, b, interpret=True)
    assert log[0].kind == "tsm2l" and log[0].shape == (4096, 16, 8)
    want = jnp.einsum("bmk,kn->bmn", a, b)
    assert got.shape == (4, 1024, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_batched_tsmm_grad_matches_oracle():
    a, b = _rand(6, (2, 2048, 16)), _rand(7, (16, 8))
    def loss(fn):
        return lambda a_, b_: jnp.sum(jnp.tanh(fn(a_, b_)))

    da, db = jax.grad(loss(lambda a_, b_: tsmm.tsmm(a_, b_, interpret=True)),
                      (0, 1))(a, b)
    ra, rb = jax.grad(loss(lambda a_, b_: jnp.einsum("bmk,kn->bmn", a_, b_)),
                      (0, 1))(a, b)
    np.testing.assert_allclose(np.asarray(da), np.asarray(ra), **TOL)
    np.testing.assert_allclose(np.asarray(db), np.asarray(rb), **TOL)


def test_batched_tsmm_t_matches_oracle():
    x, y = _rand(8, (2, 2048, 32)), _rand(9, (2, 2048, 8))
    with tsmm.record_dispatches() as log:
        got = tsmm.tsmm_t(x, y, interpret=True)
    assert log[0].kind == "tsmt" and log[0].shape == (4096, 32, 8)
    want = x.reshape(-1, 32).T @ y.reshape(-1, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_batched_dense_path_is_reshape_free_and_correct():
    a = _rand(10, (2, 64, 128))        # too small: dense
    b = _rand(11, (128, 512))
    with tsmm.record_dispatches() as log:
        got = tsmm.tsmm(a, b)
    assert [e.executor for e in log] == ["dense-xla"]
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(jnp.einsum("bmk,kn->bmn", a, b)),
                               rtol=1e-4, atol=1e-4)


def test_shape_validation():
    with pytest.raises(ValueError, match="lhs"):
        tsmm.tsmm(_rand(0, (8,)), _rand(1, (8, 4)))
    with pytest.raises(ValueError, match="contraction mismatch"):
        tsmm.tsmm(_rand(0, (8, 16)), _rand(1, (8, 4)))
    with pytest.raises(ValueError, match="identical leading dims"):
        tsmm.tsmm_t(_rand(0, (2, 64, 8)), _rand(1, (3, 64, 4)))


# ---------------------------------------------------------------------------
# Registry + executor pinning
# ---------------------------------------------------------------------------

def test_builtin_executors_registered():
    names = set(tsmm.executors())
    assert {"pallas-tpu", "interpret", "dense-xla", "shard_map"} <= names


def test_register_and_pin_custom_executor():
    calls = []

    def traced_dense(entry, kind, a, b, p):
        calls.append((entry, kind))
        return tsmm.executors()["dense-xla"](entry, kind, a, b, p)

    tsmm.register_executor("test-dense", traced_dense)
    try:
        with pytest.raises(ValueError, match="already registered"):
            tsmm.register_executor("test-dense", traced_dense)
        a, b = _rand(12, (4096, 16)), _rand(13, (16, 8))
        with tsmm.policy(executor="test-dense"):
            out = tsmm.tsmm(a, b)
        assert calls == [("mm", "tsm2l")]
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(ref.tsm2l_ref(a, b)), **TOL)
    finally:
        tsmm.unregister_executor("test-dense")
    assert "test-dense" not in tsmm.executors()


def test_unregistered_executor_pin_raises():
    a, b = _rand(14, (4096, 16)), _rand(15, (16, 8))
    with tsmm.policy(executor="nope"):
        with pytest.raises(ValueError, match="not registered"):
            tsmm.tsmm(a, b)


def test_interpret_policy_field_selects_interpret_executor():
    a, b = _rand(16, (4096, 16)), _rand(17, (16, 8))
    with tsmm.policy(interpret=True):
        with tsmm.record_dispatches() as log:
            tsmm.tsmm(a, b)
    assert [e.executor for e in log] == ["interpret"]


def test_backward_policy_strips_force_and_executor():
    p = tsmm.GemmPolicy(mode="tsm2r", executor="interpret")
    bp = tsmm.backward_policy(p)
    assert bp.mode == "auto" and bp.executor is None
    dense = tsmm.GemmPolicy(mode="dense")
    assert tsmm.backward_policy(dense) is dense


def test_backward_honors_dense_scope():
    """grad of a tsmm traced under mode='dense' stays dense end to end."""
    a, b = _rand(18, (4096, 16)), _rand(19, (16, 8))
    with tsmm.policy(mode="dense"):
        with tsmm.record_dispatches() as log:
            jax.grad(lambda a_: jnp.sum(tsmm.tsmm(a_, b)))(a)
    assert {e.executor for e in log} == {"dense-xla"}


def test_enabled_is_policy_alias():
    assert tsmm.enabled()
    with tsmm.policy(mode="dense"):
        assert not tsmm.enabled()


# ---------------------------------------------------------------------------
# Benchmark report plumbing (the --json surface)
# ---------------------------------------------------------------------------

def test_bench_report_shape(tmp_path):
    import importlib
    import json
    import pathlib
    import sys

    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root))
    try:
        run_mod = importlib.import_module("benchmarks.run")
    finally:
        sys.path.remove(str(root))
    report = run_mod.build_report(
        {"sec": ("ok", [("row_a", 1.5, "kind=tsm2r"), ("row_b", "n/a")])})
    assert report["schema"].startswith("repro-tsm2x-bench/")
    assert report["policy"]["mode"] == tsmm.current_policy().mode
    rows = report["sections"]["sec"]["rows"]
    assert rows[0] == {"name": "row_a", "us_per_call": 1.5,
                       "derived": "kind=tsm2r"}
    assert rows[1]["us_per_call"] is None
    kinds = {(c["m"], c["k"], c["n"]): c["kind"]
             for c in report["classification"]}
    assert kinds[(20480, 20480, 2)] == "tsm2r"
    assert kinds[(4096, 4096, 1024)] == "dense"
    (tmp_path / "BENCH_test.json").write_text(json.dumps(report))


# ---------------------------------------------------------------------------
# reduce= knob + mesh-derived dp_axes (PR 4)
# ---------------------------------------------------------------------------

def test_reduce_validation():
    assert tsmm.GemmPolicy(reduce="psum_scatter").reduce == "psum_scatter"
    with pytest.raises(ValueError, match="psum_scatter"):
        tsmm.GemmPolicy(reduce="allreduce")


def test_backward_policy_keeps_scatter_downgrades_none():
    p = tsmm.GemmPolicy(reduce="psum_scatter")
    assert tsmm.backward_policy(p).reduce == "psum_scatter"
    assert tsmm.backward_policy(p) is p  # nothing to strip: same object
    p_none = tsmm.GemmPolicy(reduce="none", mode="tsm2r", executor="interpret")
    bp = tsmm.backward_policy(p_none)
    assert bp.reduce == "psum"           # stacked partials can't be a cotangent
    assert bp.mode == "auto" and bp.executor is None


def test_scatter_executor_registered_and_mmt_only():
    assert "shard_map-scatter" in tsmm.executors()
    a = jnp.ones((4096, 512), jnp.bfloat16)
    b = jnp.ones((512, 8), jnp.bfloat16)
    with tsmm.policy(executor="shard_map-scatter"):
        with pytest.raises(RuntimeError, match="only applies to tsmm_t"):
            tsmm.tsmm(a, b)


def test_derive_dp_axes_rules():
    am = compat.abstract_mesh
    # single non-model-named axis is DP, whatever the name
    assert tsmm.derive_dp_axes(am((8,), ("anything",))) == ("anything",)
    # ...but a lone model-named axis is pure TP, never DP
    assert tsmm.derive_dp_axes(am((8,), ("model",))) == ()
    assert tsmm.derive_dp_axes(am((8,), ("tp",))) == ()
    # conventional names win, mesh order preserved
    assert tsmm.derive_dp_axes(am((2, 4, 2), ("pod", "data", "model"))) \
        == ("pod", "data")
    assert tsmm.derive_dp_axes(am((4, 2), ("batch", "model"))) == ("batch",)
    # no conventional name: everything not model/pipeline-named is DP
    assert tsmm.derive_dp_axes(am((4, 2), ("nodes", "tensor"))) == ("nodes",)
    # pure model/pipe mesh: no DP axes at all
    assert tsmm.derive_dp_axes(am((4, 2), ("model", "pipe"))) == ()
    # distributed.sharding shares the derivation
    from repro.distributed import sharding
    assert sharding.dp_axes(am((2, 2), ("replica", "model"))) == ("replica",)


def test_reduce_has_no_effect_off_mesh():
    a = jnp.ones((4096, 512), jnp.bfloat16)
    b = jnp.ones((512, 8), jnp.bfloat16)
    with tsmm.policy(reduce="psum_scatter"):
        with tsmm.record_dispatches() as log:
            jax.jit(lambda a_, b_: tsmm.tsmm(a_, b_)).lower(a, b)
    assert {e.executor for e in log} == {"pallas-tpu"}


def test_executor_pin_collective_mismatch_raises():
    """A pinned shard_map executor must refuse a mismatched reduce= rather
    than silently changing the output layout the scope asked for."""
    from jax.sharding import Mesh

    x = jnp.ones((4096, 64), jnp.float32)
    y = jnp.ones((4096, 8), jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    with jax.set_mesh(mesh):
        with tsmm.policy(executor="shard_map", reduce="psum_scatter"):
            with pytest.raises(RuntimeError, match="shard_map-scatter"):
                tsmm.tsmm_t(x, y)
        with tsmm.policy(executor="shard_map-scatter"):  # default psum
            with pytest.raises(RuntimeError, match="psum_scatter"):
                tsmm.tsmm_t(x, y)


def test_context_mesh_follows_set_mesh():
    """compat.get_context_mesh reads the jax.set_mesh scope (eagerly and
    under jit) and reports no mesh inside a shard_map body, where the
    dispatcher must treat shapes as local."""
    from jax.sharding import PartitionSpec as P

    mesh = compat.make_mesh((1,), ("data",))
    assert compat.get_context_mesh() is None
    seen = {}

    def body(x):
        seen["body"] = compat.get_context_mesh()
        return x

    def traced(x):
        seen["jit"] = compat.get_context_mesh()
        return compat.shard_map(body, mesh=mesh, in_specs=P("data"),
                                out_specs=P("data"))(x)

    with jax.set_mesh(mesh):
        eager = compat.get_context_mesh()
        jax.jit(traced)(jnp.ones((4,)))
    assert eager is not None and eager.axis_names == ("data",)
    assert compat.mesh_axis_sizes(eager) == {"data": 1}
    assert seen["jit"] is not None and seen["body"] is None
    assert compat.get_context_mesh() is None


def test_repo_imports_raise_no_deprecation_warning():
    """Every module of the package imports cleanly with JAX's deprecation
    warnings turned into errors (no deprecated JAX spellings remain)."""
    import pathlib
    import subprocess
    import sys

    src = pathlib.Path(__file__).resolve().parents[1] / "src"
    mods = sorted(
        ".".join(p.relative_to(src).with_suffix("").parts)
        for p in (src / "repro").rglob("*.py")
        # dryrun rewrites XLA_FLAGS/JAX_PLATFORMS as it is imported
        if p.name != "__init__.py" and p.name != "dryrun.py")
    code = "import importlib\n" + "".join(
        f"importlib.import_module({m!r})\n" for m in mods)
    r = subprocess.run(
        [sys.executable, "-W", "error::DeprecationWarning", "-c", code],
        env={"PYTHONPATH": str(src), "JAX_PLATFORMS": "cpu",
             "PATH": "/usr/bin:/bin"},
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
