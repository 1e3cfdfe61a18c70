"""Grid-dataflow verifier (`repro.analysis.kernel_verify`).

Three tiers, mirroring tests/test_contracts.py:

* capture units -- the compat.pallas_call shim records exactly the launch
  the committed entries construct (grid, specs, semantics, scratch), and
  corner sampling kicks in above the cell limit;
* acceptance -- seeded-broken kernels (swapped output index map, missing
  pl.when init guard, parallel tag on the reduction dim, bf16 scratch
  accumulator, out-of-bounds map, unguarded flush) are each rejected with
  the right rule id;
* clean tree -- every committed kernel at representative configs, and the
  full audit_kernel_dataflow sweep arm, verify clean.
"""

import math

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl

from repro.analysis import audit, contracts, kernel_verify
from repro.core import perf_model
from repro.kernels import compat

F32 = jnp.float32
BF16 = jnp.bfloat16


def _rules(violations):
    return [v.rule for v in violations]


def _capture_one(build, *operands):
    """LaunchCapture of a single compat.pallas_call launch, traced
    abstractly (the same path capture_kernel takes for committed entries).

    ``build`` is a zero-arg callable constructing the launch: the shim
    decides whether to record at *construction* time, so the build must
    happen inside the capture scope (as the committed entries' do)."""
    with compat.capture_launches() as log:
        jax.eval_shape(build(), *operands)
    assert len(log) == 1, log
    return log[0]


# ---------------------------------------------------------------------------
# Capture units
# ---------------------------------------------------------------------------

def test_capture_records_committed_tsm2r_launch():
    caps = kernel_verify.capture_kernel(
        "tsm2r", (256, 512, 8), {"block_m": 64, "block_k": 128}, F32)
    assert len(caps) == 1
    cap = caps[0]
    assert cap.name == "_tsm2r_kernel"
    assert cap.grid == (4, 4)
    assert cap.dimension_semantics == ("parallel", "arbitrary")
    assert [s.block_shape for s in cap.in_specs] == [(64, 128), (128, 8)]
    assert [tuple(o.shape) for o in cap.operands] == [(256, 512), (512, 8)]
    assert [tuple(o.shape) for o in cap.out_shapes] == [(256, 8)]
    (scratch,) = cap.scratch_shapes
    assert tuple(scratch.shape) == (64, 8)
    assert jnp.dtype(scratch.dtype) == F32
    # index maps are the raw callables, evaluable with plain ints
    assert cap.in_specs[0].index_map(2, 3) == (2, 3)
    assert cap.out_specs[0].index_map(2, 3) == (2, 0)


def test_capture_is_scoped_and_nested():
    with compat.capture_launches() as outer:
        kernel_verify.capture_kernel("tsm2l", (128, 16, 8),
                                     {"block_m": 64}, F32)
    # capture_kernel opened its own inner scope; nothing leaks outward
    assert outer == []


def test_sample_cells_exhaustive_and_corner():
    cells, exhaustive = kernel_verify.sample_cells((4, 4))
    assert exhaustive and len(cells) == 16
    big = (128, 64)   # 8192 cells > EXHAUSTIVE_CELL_LIMIT
    assert math.prod(big) > kernel_verify.EXHAUSTIVE_CELL_LIMIT
    cells, exhaustive = kernel_verify.sample_cells(big)
    assert not exhaustive and len(cells) <= 5 ** len(big)
    for d, g in enumerate(big):   # corners per dim: 0, 1, mid, last-1, last
        assert {0, 1, g // 2, g - 2, g - 1} == {c[d] for c in cells}


# ---------------------------------------------------------------------------
# Seeded-broken kernels: each mutation rejected with its rule id
# ---------------------------------------------------------------------------

BM, BK, N = 64, 128, 8
M, K = 4 * BM, 4 * BK
A_SDS = jax.ShapeDtypeStruct((M, K), F32)
B_SDS = jax.ShapeDtypeStruct((K, N), F32)


def _tsm2r_like_launch(kernel, *, out_map, semantics=("parallel", "arbitrary"),
                       scratch_dtype=F32, out_dtype=F32, scratch=True):
    return compat.pallas_call(
        kernel,
        grid=(M // BM, K // BK),
        in_specs=[
            pl.BlockSpec((BM, BK), lambda i, j: (i, j)),
            pl.BlockSpec((BK, N), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((BM, N), out_map),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dtype),
        scratch_shapes=(
            [compat.VMEM((BM, N), scratch_dtype)] if scratch else []),
        compiler_params=compat.CompilerParams(dimension_semantics=semantics),
        interpret=True,
    )


def _good_kernel(a_ref, b_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def test_healthy_launch_verifies_clean():
    cap = _capture_one(
        lambda: _tsm2r_like_launch(_good_kernel, out_map=lambda i, j: (i, 0)),
        A_SDS, B_SDS)
    assert kernel_verify.verify_capture(cap) == []


def test_swapped_output_index_map_is_a_write_race():
    """Mutation 1: out map (j, 0) instead of (i, 0) -- cells that differ
    in the parallel m dim land on the same output block."""
    cap = _capture_one(
        lambda: _tsm2r_like_launch(_good_kernel, out_map=lambda i, j: (j, 0)),
        A_SDS, B_SDS)
    assert "write-race" in _rules(kernel_verify.verify_capture(cap))


def test_parallel_tag_on_reduction_dim_is_a_write_race():
    """Mutation 2: dimension_semantics ("parallel", "parallel") on the
    sequential-reduction kernel -- the k revisits now race."""
    cap = _capture_one(
        lambda: _tsm2r_like_launch(_good_kernel, out_map=lambda i, j: (i, 0),
                           semantics=("parallel", "parallel")),
        A_SDS, B_SDS)
    vios = kernel_verify.verify_capture(cap)
    assert _rules(vios) == ["write-race"]
    assert "parallel dims [0, 1]" in vios[0].detail


def test_missing_init_guard_is_revisit_init():
    """Mutation 3: direct-accumulation kernel without the
    pl.when(program_id == 0) zero-init."""
    def _no_init(a_ref, b_ref, o_ref):
        o_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                              preferred_element_type=jnp.float32)

    cap = _capture_one(
        lambda: _tsm2r_like_launch(_no_init, out_map=lambda i, j: (i, 0),
                           scratch=False),
        A_SDS, B_SDS)
    vios = kernel_verify.verify_capture(cap)
    assert _rules(vios) == ["revisit-init"]
    assert "pl.when(pl.program_id(1) == 0)" in vios[0].detail


def test_bf16_scratch_accumulator_rejected():
    """Mutation 4: bf16 VMEM scratch -- partial accumulators must be f32
    regardless of operand dtype."""
    def _bf16_acc(a_ref, b_ref, o_ref, acc_ref):
        @pl.when(pl.program_id(1) == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(a_ref[...],
                                b_ref[...]).astype(acc_ref.dtype)

        @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
        def _flush():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    cap = _capture_one(
        lambda: _tsm2r_like_launch(_bf16_acc, out_map=lambda i, j: (i, 0),
                                   scratch_dtype=BF16),
        A_SDS, B_SDS)
    assert "accumulator-dtype" in _rules(kernel_verify.verify_capture(cap))


def test_bf16_revisited_output_accumulator_rejected():
    """Same family, other site: a direct-accumulation kernel whose
    revisited *output* is bf16."""
    def _init_ok(a_ref, b_ref, o_ref):
        @pl.when(pl.program_id(1) == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[...] += jnp.dot(a_ref[...], b_ref[...]).astype(o_ref.dtype)

    cap = _capture_one(
        lambda: _tsm2r_like_launch(_init_ok, out_map=lambda i, j: (i, 0),
                           scratch=False, out_dtype=BF16),
        A_SDS, B_SDS)
    assert _rules(kernel_verify.verify_capture(cap)) == ["accumulator-dtype"]


def test_out_of_bounds_index_map_rejected():
    """Mutation 5: off-by-one block offset reaches past the padded dim."""
    cap = _capture_one(
        lambda: _tsm2r_like_launch(_good_kernel, out_map=lambda i, j: (i + 1, 0)),
        A_SDS, B_SDS)
    vios = kernel_verify.verify_capture(cap)
    assert "index-bounds" in _rules(vios)


def test_unguarded_flush_is_revisit_flush():
    """Mutation 6: scratch-staged kernel writing the output every step
    instead of under the last-step flush guard."""
    def _no_flush(a_ref, b_ref, o_ref, acc_ref):
        @pl.when(pl.program_id(1) == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                                preferred_element_type=jnp.float32)
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    cap = _capture_one(
        lambda: _tsm2r_like_launch(_no_flush, out_map=lambda i, j: (i, 0)),
        A_SDS, B_SDS)
    assert _rules(kernel_verify.verify_capture(cap)) == ["revisit-flush"]


def test_missing_scratch_init_behind_good_flush_is_revisit_init():
    """The flush guard alone is not enough: the scratch accumulator still
    needs its first-step zero-init."""
    def _no_scratch_init(a_ref, b_ref, o_ref, acc_ref):
        acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                                preferred_element_type=jnp.float32)

        @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
        def _flush():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    cap = _capture_one(
        lambda: _tsm2r_like_launch(_no_scratch_init, out_map=lambda i, j: (i, 0)),
        A_SDS, B_SDS)
    vios = kernel_verify.verify_capture(cap)
    assert _rules(vios) == ["revisit-init"]
    assert "scratch acc_ref" in vios[0].detail


def test_lambda_kernel_guard_unverifiable():
    """A revisited output whose kernel body can't be AST-inspected is
    reported, not silently passed."""
    cap = _capture_one(
        lambda: _tsm2r_like_launch(
            eval("lambda a_ref, b_ref, o_ref, acc_ref: None"),
            out_map=lambda i, j: (i, 0)),
        A_SDS, B_SDS)
    assert "guard-unverifiable" in _rules(kernel_verify.verify_capture(cap))


def test_semantics_arity_mismatch_rejected():
    cap = _capture_one(
        lambda: _tsm2r_like_launch(_good_kernel, out_map=lambda i, j: (i, 0),
                           semantics=("parallel",)),
        A_SDS, B_SDS)
    assert _rules(kernel_verify.verify_capture(cap)) == ["semantics-invalid"]


def test_corner_sampling_still_catches_swapped_map():
    """Above the cell limit the verifier samples corners -- and the
    swapped-map race is still caught there."""
    m, k = 128 * BM, 64 * BK   # grid (128, 64): 8192 cells, sampled
    cap = _capture_one(
        lambda: compat.pallas_call(
            _good_kernel,
            grid=(m // BM, k // BK),
            in_specs=[
                pl.BlockSpec((BM, BK), lambda i, j: (i, j)),
                pl.BlockSpec((BK, N), lambda i, j: (j, 0)),
            ],
            out_specs=pl.BlockSpec((BM, N), lambda i, j: (j % 2, 0)),
            out_shape=jax.ShapeDtypeStruct((m, N), F32),
            scratch_shapes=[compat.VMEM((BM, N), F32)],
            compiler_params=compat.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=True,
        ),
        jax.ShapeDtypeStruct((m, k), F32), jax.ShapeDtypeStruct((k, N), F32))
    _, exhaustive = kernel_verify.sample_cells(cap.grid)
    assert not exhaustive
    assert "write-race" in _rules(kernel_verify.verify_capture(cap))


# ---------------------------------------------------------------------------
# verify_kernel_config: capture plumbing + launch-meta drift
# ---------------------------------------------------------------------------

COMMITTED_CONFIGS = [
    ("tsm2r", (256, 512, 8), {"block_m": 64, "block_k": 128}),
    ("tsm2r", (256, 512, 8), {"block_m": 64, "block_k": 128, "splits": 2}),
    ("tsm2l", (256, 16, 8), {"block_m": 64}),
    ("tsmt", (256, 16, 16), {"block_m": 64, "block_a": 8}),
    ("tsmt", (256, 16, 16), {"block_m": 64, "block_a": 8, "splits": 2}),
    ("reduce", (4, 256, 128), {"block_r": 64}),
]


@pytest.mark.parametrize("kind,padded,params", COMMITTED_CONFIGS,
                         ids=[f"{k}-{'split' if dict(p).get('splits', 1) > 1 else 'seq'}"
                              for k, _, p in COMMITTED_CONFIGS])
@pytest.mark.parametrize("dtype", [BF16, F32])
def test_committed_kernels_verify_clean(kind, padded, params, dtype):
    vios, info = kernel_verify.verify_kernel_config(kind, padded, params,
                                                    dtype)
    assert vios == [], "\n".join(str(v) for v in vios)
    assert info["launches"] == 1 and info["exhaustive"]
    assert info["grid"] == contracts.launch_grid(kind, padded, params)[0]


def test_launch_meta_drift_detected(monkeypatch):
    """If the pure launch_grid derivation stops matching the real launch,
    verify_kernel_config says so (the DispatchEvent metadata would lie)."""
    real = contracts.launch_grid

    def skewed(kind, padded_shape, params):
        grid, sem = real(kind, padded_shape, params)
        return (grid[:-1] + (grid[-1] + 1,)), sem

    monkeypatch.setattr(contracts, "launch_grid", skewed)
    vios, _ = kernel_verify.verify_kernel_config(
        "tsm2l", (256, 16, 8), {"block_m": 64}, F32)
    assert _rules(vios) == ["launch-meta-drift"]


def test_capture_empty_reported(monkeypatch):
    """An entry that bypasses compat.pallas_call produces no capture --
    reported as capture-empty, not silently passed."""
    from repro.kernels import tsm2l

    def raw_entry(a, b, *, block_m, interpret, vmem_limit_bytes):
        return jnp.zeros((a.shape[0], b.shape[1]), a.dtype)

    monkeypatch.setattr(tsm2l, "tsm2l_pallas", raw_entry)
    vios, info = kernel_verify.verify_kernel_config(
        "tsm2l", (256, 16, 8), {"block_m": 64}, F32)
    assert _rules(vios) == ["capture-empty"]
    assert info["launches"] == 0


# ---------------------------------------------------------------------------
# Audit integration
# ---------------------------------------------------------------------------

SMALL_SHAPES = {
    "tsm2r": ((2048, 512, 8),),
    "tsm2l": ((8192, 16, 16),),
    "tsmt": ((4096, 64, 8),),
}


def test_audit_kernel_dataflow_small_sweep_clean():
    checked, vios, meta = audit.audit_kernel_dataflow(
        shapes=SMALL_SHAPES, dtypes=(F32,), specs=(perf_model.V5E,),
        splits=("auto", 2))
    assert vios == [], "\n".join(str(v) for v in vios)
    assert checked > 0
    assert meta["cell_limit"] == kernel_verify.EXHAUSTIVE_CELL_LIMIT
    assert isinstance(meta["sampled"], list)


def test_audit_report_carries_kernel_dataflow_section():
    report = audit.run_audit(shapes=SMALL_SHAPES)
    sec = report["sections"]["kernel-dataflow"]
    assert sec["checked"] > 0 and sec["violations"] == []
    assert sec["cell_limit"] == kernel_verify.EXHAUSTIVE_CELL_LIMIT
    assert "sampled" in sec
