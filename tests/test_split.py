"""Split-reduction (split-K) coverage: oracle equality of the split
kernels across odd shapes, the GemmPolicy.split knob end-to-end (kernel
spies + dispatch events), backward_policy semantics, the tsmt accumulator
limit, and the partials tree-reduce epilogue.

The split kernels accumulate each reduction slice in f32 and the epilogue
sums the (S, ...) f32 stack, so split outputs match the sequential kernels
up to one final reassociation -- tolerances here are the same as the
sequential-vs-oracle ones in tests/test_kernels.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import tsmm
from repro.kernels import ops, ref
from repro.kernels.reduce import (JNP_REDUCE_MAX_ELEMS, reduce_partials,
                                  sum_partials_pallas)

jax.config.update("jax_enable_x64", False)


def _rand(seed, shape, dtype=jnp.float32):
    x = jax.random.uniform(jax.random.PRNGKey(seed), shape, jnp.float32,
                           minval=-1.0, maxval=1.0)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# Oracle equality (split == sequential == jnp reference)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("splits", [2, 4, 8])
@pytest.mark.parametrize("m,a,b", [
    (8192, 128, 8),       # PowerSGD Q = G^T P with r=8
    (10000, 300, 16),     # non-divisible everywhere
    (4100, 1, 1),         # degenerate skinny: the occupancy-starved case
])
def test_tsmt_split_matches_sequential(m, a, b, splits):
    x, y = _rand(m + a, (m, a)), _rand(m + b, (m, b))
    seq = ops.tsmt(x, y, splits=1, interpret=True)
    got = ops.tsmt(x, y, splits=splits, interpret=True)
    np.testing.assert_allclose(got, seq, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got, ref.tsmt_ref(x, y), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("splits", [2, 4])
@pytest.mark.parametrize("m,k,n", [
    (2048, 1024, 4),
    (1000, 777, 16),      # padding on both m and k
])
def test_tsm2r_split_matches_sequential(m, k, n, splits):
    a, b = _rand(m + k, (m, k)), _rand(m + n, (k, n))
    seq = ops.tsm2r(a, b, splits=1, interpret=True)
    got = ops.tsm2r(a, b, splits=splits, interpret=True)
    np.testing.assert_allclose(got, seq, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(got, ref.tsm2r_ref(a, b), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tsmt_split_dtypes(dtype):
    x, y = _rand(0, (8192, 16), dtype), _rand(1, (8192, 16), dtype)
    got = ops.tsmt(x, y, splits=4, interpret=True)
    tol = (dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16
           else dict(rtol=1e-4, atol=1e-4))
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref.tsmt_ref(x, y), np.float32),
                               **tol)


@settings(max_examples=15, deadline=None)
@given(m=st.integers(257, 3000), a=st.integers(1, 64), b=st.integers(1, 16),
       splits=st.sampled_from([2, 4, 8]),
       seed=st.integers(0, 2**31 - 1))
def test_tsmt_split_oracle_property(m, a, b, splits, seed):
    """Odd shapes (m a non-multiple of S*bm more often than not, a=1/b=1
    included): split output == f32-accumulated oracle."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.uniform(k1, (m, a), jnp.float32, -1, 1)
    y = jax.random.uniform(k2, (m, b), jnp.float32, -1, 1)
    got = ops.tsmt(x, y, block_m=256, block_a=64, splits=splits,
                   interpret=True)
    np.testing.assert_allclose(got, ref.tsmt_ref(x, y), rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(m=st.integers(64, 800), k=st.integers(130, 700),
       n=st.integers(1, 16), splits=st.sampled_from([2, 4]),
       seed=st.integers(0, 2**31 - 1))
def test_tsm2r_split_oracle_property(m, k, n, splits, seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    a = jax.random.uniform(k1, (m, k), jnp.float32, -1, 1)
    b = jax.random.uniform(k2, (k, n), jnp.float32, -1, 1)
    got = ops.tsm2r(a, b, block_m=256, block_k=128, splits=splits,
                    interpret=True)
    np.testing.assert_allclose(got, ref.tsm2r_ref(a, b), rtol=1e-4,
                               atol=1e-4)


# ---------------------------------------------------------------------------
# The policy knob, end-to-end (kernel spies + dispatch events)
# ---------------------------------------------------------------------------

@pytest.fixture
def tsmt_split_spy(monkeypatch):
    calls = {"split": [], "seq": 0}
    orig_split = ops.tsmt_pallas_split
    orig_seq = ops.tsmt_pallas

    def spy_split(x, y, *, block_m, block_a, splits, interpret,
                  vmem_limit_bytes):
        calls["split"].append(splits)
        return orig_split(x, y, block_m=block_m, block_a=block_a,
                          splits=splits, interpret=interpret,
                          vmem_limit_bytes=vmem_limit_bytes)

    def spy_seq(x, y, *, block_m, block_a, interpret, vmem_limit_bytes):
        calls["seq"] += 1
        return orig_seq(x, y, block_m=block_m, block_a=block_a,
                        interpret=interpret,
                        vmem_limit_bytes=vmem_limit_bytes)

    monkeypatch.setattr(ops, "tsmt_pallas_split", spy_split)
    monkeypatch.setattr(ops, "tsmt_pallas", spy_seq)
    return calls


def test_policy_split_pin_reaches_the_kernel(tsmt_split_spy):
    x, y = _rand(0, (4096, 64)), _rand(1, (4096, 8))
    with tsmm.policy(split=4, interpret=True):
        got = tsmm.tsmm_t(x, y)
    assert tsmt_split_spy["split"] == [4] and tsmt_split_spy["seq"] == 0
    np.testing.assert_allclose(got, ref.tsmt_ref(x, y), rtol=1e-4, atol=1e-4)


def test_policy_split_never_forces_sequential(tsmt_split_spy):
    x, y = _rand(2, (4096, 64)), _rand(3, (4096, 8))
    # even a tuning-table winner with splits > 1 must not override "never"
    from repro.core import autotune
    rec = autotune.TuningRecord(
        kind="tsmt", bucket=autotune.bucket_shape(4096, 64, 8),
        dtype="float32", spec_name="tpu_v5e", executor="interpret",
        shape=(4096, 64, 8),
        params=(("block_a", 128), ("block_m", 256), ("splits", 4)),
        measured_us=1.0, model_us=1.0, model_error=0.0,
        model_pick=(("block_a", 128), ("block_m", 256), ("splits", 4)),
        model_pick_measured_us=1.0)
    tbl = autotune.TuningTable.from_records([rec])
    with tsmm.policy(split="never", tuning_table=tbl, interpret=True):
        tsmm.tsmm_t(x, y)
    assert tsmt_split_spy["split"] == [] and tsmt_split_spy["seq"] == 1


def test_tuning_table_splits_drive_dispatch(tsmt_split_spy):
    """An "auto" scope consumes the measured splits from the table."""
    from repro.core import autotune
    rec = autotune.TuningRecord(
        kind="tsmt", bucket=autotune.bucket_shape(4096, 64, 8),
        dtype="float32", spec_name="tpu_v5e", executor="interpret",
        shape=(4096, 64, 8),
        params=(("block_a", 128), ("block_m", 256), ("splits", 2)),
        measured_us=1.0, model_us=1.0, model_error=0.0,
        model_pick=(("block_a", 128), ("block_m", 256), ("splits", 2)),
        model_pick_measured_us=1.0)
    tbl = autotune.TuningTable.from_records([rec])
    x, y = _rand(4, (4096, 64)), _rand(5, (4096, 8))
    with tsmm.policy(tuning_table=tbl, interpret=True):
        got = tsmm.tsmm_t(x, y)
    assert tsmt_split_spy["split"] == [2]
    np.testing.assert_allclose(got, ref.tsmt_ref(x, y), rtol=1e-4, atol=1e-4)


def test_explicit_splits_kwarg_beats_policy(tsmt_split_spy):
    x, y = _rand(6, (4096, 64)), _rand(7, (4096, 8))
    with tsmm.policy(split=8, interpret=True):
        ops.tsmt(x, y, splits=2)
    assert tsmt_split_spy["split"] == [2]


def test_splits_clamped_to_whole_slices(tsmt_split_spy):
    """S is clamped so every reduction slice owns >= one block: a split=16
    pin on a 2-block-deep m sweep runs S=2, not 16x zero-padding."""
    x, y = _rand(8, (512, 64)), _rand(9, (512, 8))
    ops.tsmt(x, y, block_m=256, block_a=64, splits=16, interpret=True)
    assert tsmt_split_spy["split"] == [2]


def test_dispatch_event_records_split_knob():
    x, y = _rand(10, (4096, 64)), _rand(11, (4096, 8))
    with tsmm.policy(split=4, interpret=True):
        with tsmm.record_dispatches() as log:
            tsmm.tsmm_t(x, y)
    assert [e.split for e in log] == [4]
    # The event also carries the launch metadata of the real grid: the
    # split tsmt kernel ran with S=4 leading parallel slices.
    (event,) = log
    meta = event.launches[0]
    assert meta.kind == "tsmt" and meta.splits == 4
    assert len(meta.grid) == 3 and meta.grid[0] == 4
    assert meta.dimension_semantics == ("parallel", "parallel", "arbitrary")
    with tsmm.record_dispatches() as log:
        with tsmm.policy(interpret=True):
            tsmm.tsmm_t(x, y)
    assert [e.split for e in log] == ["auto"]
    assert all(lm.kind in ("tsmt", "reduce")
               for e in log for lm in e.launches)


def test_dispatch_event_launch_grid_matches_contract():
    """The grid/semantics stamped on DispatchEvent.launches equal the pure
    contracts.launch_grid derivation for the same padded shape -- the
    invariant kernel_verify enforces as launch-meta-drift over the audit
    sweep, spot-checked here end-to-end through dispatch."""
    from repro.analysis import audit, contracts

    shape = (4096, 64, 8)
    pol = tsmm.GemmPolicy(split=2, interpret=True)
    params = ops.resolve_params("tsmt", *shape, jnp.float32, pol,
                                interpret=True)
    padded = audit._padded_shape("tsmt", shape, params)
    want = contracts.launch_grid("tsmt", padded, params)

    x, y = _rand(12, (4096, 64)), _rand(13, (4096, 8))
    with tsmm.policy(split=2, interpret=True):
        with tsmm.record_dispatches() as log:
            tsmm.tsmm_t(x, y)
    (event,) = log
    meta = next(lm for lm in event.launches if lm.kind == "tsmt")
    assert (meta.grid, meta.dimension_semantics) == want


# ---------------------------------------------------------------------------
# GemmPolicy.split validation + backward semantics
# ---------------------------------------------------------------------------

def test_policy_split_validation():
    assert tsmm.GemmPolicy(split="auto").split == "auto"
    assert tsmm.GemmPolicy(split=4).split == 4
    assert tsmm.GemmPolicy(split="never").split == "never"
    with pytest.raises(ValueError, match="split"):
        tsmm.GemmPolicy(split="sometimes")
    with pytest.raises(ValueError, match="split"):
        tsmm.GemmPolicy(split=0)
    with pytest.raises(ValueError, match="split"):
        tsmm.GemmPolicy(split=True)


def test_backward_policy_strips_int_split_preserves_never():
    """An int pin is shape-specific (forward shape only) -> backward goes
    back to "auto"; "never" is scope intent -> preserved; "auto" is a
    no-op (same object back)."""
    bp = tsmm.backward_policy(tsmm.GemmPolicy(split=4))
    assert bp.split == "auto"
    bp = tsmm.backward_policy(tsmm.GemmPolicy(split="never"))
    assert bp.split == "never"
    p = tsmm.GemmPolicy()
    assert tsmm.backward_policy(p) is p


def test_split_scope_grads_match_oracle():
    """Gradients under a split scope: the forward splits, the backward
    re-dispatches under "auto" (int stripped) and values match the dense
    oracle VJP."""
    x, y = _rand(12, (4096, 32)), _rand(13, (4096, 8))

    def f_split(x_, y_):
        with tsmm.policy(split=4, interpret=True):
            return tsmm.tsmm_t(x_, y_).sum()

    def f_oracle(x_, y_):
        return ref.tsmt_ref(x_, y_).sum()

    gx, gy = jax.grad(f_split, argnums=(0, 1))(x, y)
    ox, oy = jax.grad(f_oracle, argnums=(0, 1))(x, y)
    np.testing.assert_allclose(gx, ox, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(gy, oy, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# tsmt unblocked-accumulator limit (satellite)
# ---------------------------------------------------------------------------

def test_tsmt_rejects_oversized_b():
    x = jnp.zeros((4096, 8), jnp.float32)
    y = jnp.zeros((4096, ops.TSMT_MAX_B + 1), jnp.float32)
    with pytest.raises(ValueError, match="accumulator limit"):
        ops.tsmt(x, y, interpret=True)
    # at the limit it still dispatches (classifier boundary)
    ok = ops.tsmt(x, jnp.zeros((4096, ops.TSMT_MAX_B), jnp.float32),
                  interpret=True)
    assert ok.shape == (8, ops.TSMT_MAX_B)


def test_tsmt_limit_follows_raised_classifier_threshold():
    """A policy that deliberately raises max_skinny_t past TSMT_MAX_B has
    opted into the bigger accumulator tile: the guard must not crash
    shapes the scope's classifier routes to the kernel."""
    x, y = _rand(20, (4096, 8)), _rand(21, (4096, 600))
    with tsmm.policy(max_skinny_t=640, interpret=True):
        got = tsmm.tsmm_t(x, y)
    np.testing.assert_allclose(got, ref.tsmt_ref(x, y), rtol=1e-3, atol=1e-3)
    # past even the raised threshold it still raises
    with pytest.raises(ValueError, match="accumulator limit"):
        with tsmm.policy(max_skinny_t=640, interpret=True):
            ops.tsmt(x, _rand(22, (4096, 700)))


def test_tsmm_t_auto_still_degrades_dense_past_limit():
    """The dispatcher never routes b > max_skinny_t to the kernel, so the
    new guard must not break tsmm_t on such shapes."""
    x, y = _rand(14, (4096, 8)), _rand(15, (4096, 600))
    with tsmm.record_dispatches() as log:
        got = tsmm.tsmm_t(x, y, interpret=True)
    assert [e.kind for e in log] == ["dense"]
    np.testing.assert_allclose(got, ref.tsmt_ref(x, y), rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# Partials tree-reduce epilogue
# ---------------------------------------------------------------------------

def test_reduce_partials_both_paths_match():
    key = jax.random.PRNGKey(0)
    small = jax.random.normal(key, (4, 128, 8), jnp.float32)
    assert small.size <= JNP_REDUCE_MAX_ELEMS
    np.testing.assert_allclose(
        reduce_partials(small, jnp.float32, block_r=128,
                        vmem_budget=1 << 22, interpret=True),
        jnp.sum(small, axis=0), rtol=1e-6, atol=1e-6)
    big = jax.random.normal(key, (4, 1 << 16, 8), jnp.float32)
    assert big.size > JNP_REDUCE_MAX_ELEMS
    np.testing.assert_allclose(
        reduce_partials(big, jnp.float32, block_r=4096,
                        vmem_budget=1 << 22, interpret=True),
        jnp.sum(big, axis=0), rtol=1e-5, atol=1e-5)


def test_sum_partials_pallas_direct():
    p = jax.random.normal(jax.random.PRNGKey(1), (8, 256, 16), jnp.float32)
    got = sum_partials_pallas(p, block_r=64, out_dtype=jnp.float32,
                              interpret=True, vmem_limit_bytes=1 << 22)
    np.testing.assert_allclose(got, jnp.sum(p, axis=0), rtol=1e-5, atol=1e-5)
