"""Online ABFT (GemmPolicy.abft): policy plumbing, dispatch-event
stamping, zero-overhead in "none" mode, and seeded-fault chaos -- every
GEMM kind, plus the split-K and int8 executor arms -- detection under
"verify", bit-exact repair under "correct"."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import contracts
from repro.core import tsmm
from repro.ft import abft, inject


def _operands(kind, shape, key=0, dtype=jnp.float32):
    m, d1, d2 = shape
    ka, kb = jax.random.split(jax.random.PRNGKey(key))
    if kind == "tsmt":
        x = jax.random.uniform(ka, (m, d1), jnp.float32, -1, 1)
        y = jax.random.uniform(kb, (m, d2), jnp.float32, -1, 1)
    else:
        x = jax.random.uniform(ka, (m, d1), jnp.float32, -1, 1)
        y = jax.random.uniform(kb, (d1, d2), jnp.float32, -1, 1)
    return x.astype(dtype), y.astype(dtype)


def _call(kind, x, y):
    return tsmm.tsmm_t(x, y) if kind == "tsmt" else tsmm.tsmm(x, y)


def _max_cell(arr):
    r, c = np.unravel_index(np.argmax(np.abs(np.asarray(arr, np.float32))),
                            arr.shape)
    return int(r), int(c)


# -- policy plumbing --------------------------------------------------------

def test_policy_validates_abft():
    assert tsmm.GemmPolicy().abft == "none"
    for mode in ("none", "verify", "correct"):
        assert tsmm.GemmPolicy(abft=mode).abft == mode
    with pytest.raises(ValueError, match="abft"):
        tsmm.GemmPolicy(abft="retry")


def test_backward_policy_preserves_abft():
    for mode in ("none", "verify", "correct"):
        p = tsmm.GemmPolicy(abft=mode, quant="int8", split=2)
        bp = tsmm.backward_policy(p)
        assert bp.abft == mode
        assert not contracts.check_backward_policy(p, bp)


def test_policy_scope_carries_abft():
    with tsmm.policy(abft="correct"):
        assert tsmm.current_policy().abft == "correct"
    assert tsmm.current_policy().abft == "none"


# -- dispatch events --------------------------------------------------------

def test_abft_none_zero_overhead():
    x, y = _operands("tsm2r", (4096, 512, 8))
    with tsmm.record_dispatches() as log:
        with tsmm.policy(interpret=True):
            tsmm.tsmm(x, y)
    assert len(log) == 1 and log[0].abft == "none"


def test_abft_events_flag_exactly_one_guarded_dispatch():
    x, y = _operands("tsm2r", (4096, 512, 8))
    for mode in ("verify", "correct"):
        with tsmm.record_dispatches() as log:
            with tsmm.policy(interpret=True, abft=mode):
                tsmm.tsmm(x, y)
        # protected + the three checksum stages of abft_stage_shapes
        assert len(log) == 4
        flagged = [e for e in log if e.abft == mode]
        assert len(flagged) == 1 and flagged[0].kind == "tsm2r"
        assert all(e.abft == "none" for e in log if e is not flagged[0])


def test_injected_fault_stamped_on_event():
    x, y = _operands("tsm2r", (4096, 512, 8))
    f = inject.GemmFault(site=0, operand="out", row=3, col=2, bit=29)
    with tsmm.record_dispatches() as log:
        with tsmm.policy(interpret=True, abft="verify"):
            with inject.faults(f) as scope:
                tsmm.tsmm(x, y)
    assert scope.applied == [f]
    guarded = [e for e in log if e.abft == "verify"]
    assert guarded[0].faults == (f,)


# -- chaos: detect + correct per kind and executor arm ----------------------

CHAOS_ARMS = [
    ("tsm2r", (4096, 512, 8), {}),
    ("tsm2l", (8192, 16, 16), {}),
    ("tsmt", (100000, 16, 16), {}),
    ("tsm2r", (4096, 512, 8), {"split": 2}),       # split-K partials arm
    ("tsm2r", (4096, 512, 8), {"quant": "int8"}),  # quantized arm
]


@pytest.mark.parametrize("kind,shape,extra", CHAOS_ARMS,
                         ids=[f"{k}-{'-'.join(map(str, e.values())) or 'base'}"
                              for k, _, e in CHAOS_ARMS])
def test_chaos_detect_and_correct(kind, shape, extra):
    x, y = _operands(kind, shape)
    with tsmm.policy(interpret=True, **extra):
        oracle = np.asarray(_call(kind, x, y))
    # Fault the largest-|value| cell: its exponent region guarantees a
    # bit-29 flip lands far outside tolerance for every arm (including
    # int8, whose tolerance is quantization-scaled).
    r, c = _max_cell(oracle)
    fault = inject.GemmFault(site=0, operand="out", row=r, col=c, bit=29)

    # clean run under verify: bit-identical, no false positive
    with tsmm.policy(interpret=True, abft="verify", **extra):
        clean = np.asarray(_call(kind, x, y))
    np.testing.assert_array_equal(clean, oracle)

    # verify: detection = full NaN poison
    with tsmm.policy(interpret=True, abft="verify", **extra):
        with inject.faults(fault) as scope:
            poisoned = np.asarray(_call(kind, x, y))
    assert scope.applied == [fault]
    assert np.isnan(poisoned).all()

    # correct: bit-exact repair vs the fault-free oracle
    with tsmm.policy(interpret=True, abft="correct", **extra):
        with inject.faults(fault):
            fixed = np.asarray(_call(kind, x, y))
    np.testing.assert_array_equal(fixed, oracle)


@pytest.mark.parametrize("operand", ["a", "b"])
def test_operand_fault_detected(operand):
    x, y = _operands("tsm2r", (4096, 512, 8))
    f = inject.GemmFault(site=0, operand=operand, row=5, col=3, bit=29)
    with tsmm.policy(interpret=True, abft="verify"):
        with inject.faults(f):
            out = np.asarray(tsmm.tsmm(x, y))
    assert np.isnan(out).all()


def test_bf16_clean_and_corrected():
    x, y = _operands("tsm2r", (4096, 512, 8), dtype=jnp.bfloat16)
    with tsmm.policy(interpret=True):
        oracle = np.asarray(_call("tsm2r", x, y))
    with tsmm.policy(interpret=True, abft="verify"):
        clean = np.asarray(_call("tsm2r", x, y))
    np.testing.assert_array_equal(clean, oracle)
    r, c = _max_cell(oracle.astype(np.float32))
    fault = inject.GemmFault(site=0, operand="out", row=r, col=c, bit=13)
    with tsmm.policy(interpret=True, abft="correct"):
        with inject.faults(fault):
            fixed = np.asarray(_call("tsm2r", x, y))
    np.testing.assert_array_equal(fixed, oracle)


def test_grad_identity_on_clean_runs():
    x, y = _operands("tsm2r", (4096, 512, 8))

    def loss(x_, mode):
        with tsmm.policy(interpret=True, abft=mode):
            return jnp.sum(tsmm.tsmm(x_, y) ** 2)

    g_none = jax.grad(lambda x_: loss(x_, "none"))(x)
    for mode in ("verify", "correct"):
        g = jax.grad(lambda x_: loss(x_, mode))(x)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(g_none))


def test_jit_clean_path_identical():
    x, y = _operands("tsm2r", (4096, 512, 8))

    @jax.jit
    def guarded(x_, y_):
        with tsmm.policy(interpret=True, abft="correct"):
            return tsmm.tsmm(x_, y_)

    with tsmm.policy(interpret=True):
        oracle = np.asarray(tsmm.tsmm(x, y))
    np.testing.assert_array_equal(np.asarray(guarded(x, y)), oracle)


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs >= 2 devices")
def test_mesh_arm_per_shard_guard():
    """Under shard_map the outer dispatch skips the wrap; the per-shard
    re-dispatch carries the mode, so each shard's GEMM is guarded and an
    injected per-shard output fault still poisons the (replicated)
    result."""
    from jax.sharding import Mesh
    devs = jax.devices()
    m = 2048 * len(devs)
    x, y = _operands("tsmt", (m, 64, 8))
    mesh = Mesh(np.array(devs), ("data",))
    with jax.set_mesh(mesh), tsmm.policy(interpret=True, reduce="psum", abft="verify"):
        clean = np.asarray(tsmm.tsmm_t(x, y))
    with tsmm.policy(interpret=True):
        oracle = np.asarray(tsmm.tsmm_t(x, y))
    # psum reduction order differs from the single-device oracle: this
    # asserts the guard passes clean sharded runs through, not bit-equality
    np.testing.assert_allclose(clean, oracle, rtol=1e-4, atol=1e-3)
    # Site 1 is the first per-shard re-dispatch (site 0 = outer shard_map
    # invocation at the registry boundary).
    f = inject.GemmFault(site=1, operand="out", row=0, col=0, bit=29)
    with jax.set_mesh(mesh), tsmm.policy(interpret=True, reduce="psum", abft="verify"):
        with inject.faults(f):
            out = np.asarray(tsmm.tsmm_t(x, y))
    assert np.isnan(out).any()


# -- tolerance + locate-and-correct unit behavior ---------------------------

def test_tolerance_robust_to_corrupted_amax():
    """A huge faulty cell must not inflate its own column's threshold past
    its own deviation (the int8 failure mode: eps=1/127 makes the scale
    factor O(10), so an amax taken from the corrupted output would mask
    the fault entirely)."""
    eps = abft.tolerance_eps(jnp.float32, "int8")
    amax = jnp.array([40.0, 45.0, 2.4e20, 42.0], jnp.float32)
    tol = np.asarray(abft.tolerance(4096, 512, eps, amax))
    assert tol[2] < 1e7  # capped near the clean columns' scale
    clean_tol = np.asarray(abft.tolerance(
        4096, 512, eps, jnp.array([40.0, 45.0, 41.0, 42.0], jnp.float32)))
    assert (tol[2] / clean_tol[2]) < 100.0


def test_offline_correct_leaf_repairs_single_row():
    w = jax.random.normal(jax.random.PRNGKey(11), (70000, 16))
    c = abft.encode_leaf(w, interpret=True)
    bad = w.at[123, 4].add(2.0)
    ok, fixed = abft.correct_leaf(bad, c, interpret=True)
    assert not bool(ok)
    np.testing.assert_allclose(np.asarray(fixed), np.asarray(w),
                               rtol=0, atol=1e-4)
    ok2, same = abft.correct_leaf(w, c, interpret=True)
    assert bool(ok2)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(w))


def test_offline_tree_verify_and_correct():
    tree = {"w": jax.random.normal(jax.random.PRNGKey(12), (70000, 8)),
            "tiny": jnp.ones((4, 4))}  # below threshold: no checksum
    cs = abft.encode_tree(tree, interpret=True)
    ok, same = abft.verify_and_correct_tree(tree, cs, interpret=True)
    assert bool(ok)
    np.testing.assert_array_equal(np.asarray(same["w"]),
                                  np.asarray(tree["w"]))
    corrupted = {**tree, "w": tree["w"].at[7, 3].add(1.5)}
    ok2, fixed = abft.verify_and_correct_tree(corrupted, cs, interpret=True)
    assert not bool(ok2)
    np.testing.assert_allclose(np.asarray(fixed["w"]),
                               np.asarray(tree["w"]), rtol=0, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(fixed["tiny"]),
                                  np.asarray(tree["tiny"]))


def test_multi_row_fault_poisons_not_mends():
    """Two damaged rows cannot be explained by a single-row repair: the
    residual gate must reject the correction and poison instead of
    silently mis-repairing. The two faults hit different columns at
    comparable magnitudes (each column's own largest cell, distinct
    rows) -- two flips in ONE column where one deviation is orders
    smaller would be absorbed by f32 checksum rounding against the
    other, which no checksum scheme can see."""
    kind, shape = "tsm2r", (4096, 512, 8)
    x, y = _operands(kind, shape)
    with tsmm.policy(interpret=True):
        oracle = np.asarray(_call(kind, x, y))
    r0 = int(np.argmax(np.abs(oracle[:, 0])))
    col1 = np.abs(oracle[:, 1]).copy()
    col1[r0] = -np.inf  # force distinct rows: same-row damage is repairable
    r1 = int(np.argmax(col1))
    faults = (inject.GemmFault(site=0, operand="out", row=r0, col=0, bit=29),
              inject.GemmFault(site=0, operand="out", row=r1, col=1, bit=29))
    with tsmm.policy(interpret=True, abft="correct"):
        with inject.faults(*faults):
            out = np.asarray(_call(kind, x, y))
    assert np.isnan(out).all()


def test_abft_stage_shapes_contract():
    stages = contracts.abft_stage_shapes("tsm2r", (4096, 512, 8))
    assert stages == (("mmt", (4096, 512, 2)), ("mmt", (512, 8, 2)),
                      ("mmt", (4096, 8, 2)))
    stages_t = contracts.abft_stage_shapes("tsmt", (65536, 16, 16), s=3)
    assert stages_t == (("mm", (65536, 16, 3)), ("mmt", (65536, 3, 16)),
                       ("mmt", (16, 16, 3)))
    with pytest.raises(ValueError, match="s >= 2"):
        contracts.abft_stage_shapes("tsm2r", (4096, 512, 8), s=1)
    with pytest.raises(ValueError, match="unknown kind"):
        contracts.abft_stage_shapes("dense", (4096, 512, 8))


def test_abft_policy_contract_flags_drift():
    p = tsmm.GemmPolicy(abft="verify")
    drifted = dataclasses.replace(tsmm.backward_policy(p), abft="none")
    rules = [v.rule for v in contracts.check_backward_policy(p, drifted)]
    assert "abft-policy" in rules
