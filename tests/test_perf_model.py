"""Parameter-chooser contracts: candidate enumeration, VMEM feasibility,
and the documented tie-break rule (ties toward *deeper* pipelines along
the streamed/reduction axis), applied uniformly to all three choosers.

A zero-overhead spec (step_overhead = dma_latency = 0) collapses the
latency term, making every reduction-axis block size model-time-equal --
the exact boundary the tie-break rule governs. The old code preferred
*larger* block_k on ties (shallower grids) and never applied any rule to
the tsm2l/tsmt choosers.
"""

import dataclasses

import jax.numpy as jnp
import pytest

from repro.core import perf_model

ZERO_LAT = dataclasses.replace(perf_model.V5E, step_overhead=0.0,
                               dma_latency=0.0)


# ---------------------------------------------------------------------------
# Tie-break boundaries
# ---------------------------------------------------------------------------

def test_tsm2r_ties_break_toward_deeper_k_pipeline():
    """With latency terms zeroed, every feasible block_k ties (the B-refetch
    term depends only on block_m): the chooser must take the smallest
    block_k -- the deepest k-pipeline -- not the largest. Splitting is
    never a tie on a single-core spec: S > 1 adds partials traffic for no
    occupancy gain, so S == 1 wins strictly."""
    m, k, n = 8192, 2048, 8
    bm, bk, s = perf_model.choose_params_tsm2r(m, k, n, ZERO_LAT,
                                               jnp.bfloat16)
    cands = perf_model.tsm2r_candidates(m, k, n, ZERO_LAT, jnp.bfloat16)
    assert bk == min(c[1] for c in cands) == 128
    # Residual tie on block_m resolved toward fewer B-window re-fetches:
    # b_bytes scales with ceil(m/bm), so the largest bm wins *strictly*.
    assert bm == 4096
    assert s == 1


def test_tsm2r_no_tie_still_prefers_fewer_steps():
    """With real latency terms, fewer grid steps win outright -- the
    tie-break must not override a strict model-time ordering."""
    bm, bk, s = perf_model.choose_params_tsm2r(4096, 1024, 8, perf_model.V5E,
                                               jnp.bfloat16)
    assert (bm, bk, s) == (4096, 1024, 1)


def test_tsm2l_ties_break_toward_deeper_m_pipeline():
    m, k, n = 16384, 16, 16
    bm = perf_model.choose_params_tsm2l(m, k, n, ZERO_LAT, jnp.bfloat16)
    assert bm == min(perf_model.tsm2l_candidates(m, k, n, ZERO_LAT,
                                                 jnp.bfloat16)) == 256


def test_tsmt_ties_break_toward_deeper_reduction_pipeline():
    """m is the streamed reduction for TSMT: ties on block_m go to the
    smallest; block_a is resolved strictly (fewer Y re-fetches); S == 1
    wins strictly on a single-core spec (partials cost, no occupancy)."""
    m, a, b = 4096, 1024, 8
    bm, ba, s = perf_model.choose_params_tsmt(m, a, b, ZERO_LAT,
                                              jnp.bfloat16)
    assert bm == 256
    assert ba == max(c[1] for c in perf_model.tsmt_candidates(
        m, a, b, ZERO_LAT, jnp.bfloat16)) == 1024
    assert s == 1


# ---------------------------------------------------------------------------
# Candidate enumeration (the grid the autotuner shares)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind,args", [
    ("tsm2r", (20480, 20480, 16)),
    ("tsm2l", (1_000_000, 16, 16)),
    ("tsmt", (8192, 128, 8)),
])
def test_choice_is_always_a_candidate(kind, args):
    cands = getattr(perf_model, f"{kind}_candidates")(*args)
    choice = getattr(perf_model, f"choose_params_{kind}")(*args)
    assert choice in cands


def test_candidates_respect_vmem_budget():
    budget = perf_model.V5E.vmem_bytes * perf_model.V5E.vmem_usable
    for bm, bk, _ in perf_model.tsm2r_candidates(30720, 30720, 16):
        assert perf_model.tsm2r_vmem_usage(bm, bk, 16, jnp.bfloat16) <= budget
    for bm in perf_model.tsm2l_candidates(1_000_000, 16, 16):
        assert perf_model.tsm2l_vmem_usage(bm, 16, 16, jnp.bfloat16) <= budget
    for bm, ba, _ in perf_model.tsmt_candidates(8192, 512, 8):
        assert perf_model.tsmt_vmem_usage(bm, ba, 8, jnp.bfloat16) <= budget


def test_candidates_respect_shape_quantization():
    """No candidate exceeds the lane/sublane roundup of the actual dims --
    the same filter kernels/ops.py clamps the runtime blocks with."""
    for bm, bk, _ in perf_model.tsm2r_candidates(4096, 130, 8):
        assert bm <= 4096
        assert bk <= perf_model._roundup(130, perf_model.V5E.lane) == 256


def test_tiny_shape_falls_back_to_single_block():
    assert perf_model.tsm2r_candidates(64, 64, 4) == []
    bm, bk, s = perf_model.choose_params_tsm2r(64, 64, 4)
    assert (bm, bk, s) == (64, 128, 1)


# ---------------------------------------------------------------------------
# Occupancy + split-reduction (the split-K dimension of the search)
# ---------------------------------------------------------------------------

def test_split_candidates_keep_whole_reduction_slices():
    """S > 1 is only enumerated when every slice owns >= one full block of
    the reduction axis -- deeper splits would be pure zero-padding."""
    for bm, bk, s in perf_model.tsm2r_candidates(8192, 512, 8):
        assert s == 1 or s * bk <= perf_model._roundup(512, 128)
    for bm, ba, s in perf_model.tsmt_candidates(4096, 64, 8):
        assert s == 1 or s * bm <= perf_model._roundup(4096, 8)
    # and S > 1 IS reachable on both grids
    assert any(s > 1 for *_, s in perf_model.tsm2r_candidates(8192, 512, 8))
    assert any(s > 1 for *_, s in perf_model.tsmt_candidates(4096, 64, 8))


def test_occupancy_term():
    assert perf_model.occupancy(1, perf_model.V5E) == 1.0
    assert perf_model.occupancy(1, perf_model.V5P) == 0.5
    assert perf_model.occupancy(2, perf_model.V5P) == 1.0
    assert perf_model.occupancy(64, perf_model.V5P) == 1.0


def test_occupancy_model_selects_split_for_powersgd_shape():
    """The ISSUE's headline case: a PowerSGD-shaped tsmt (huge m, a = b =
    16) collapses to ONE parallel grid cell, so on the 2-core v5p the
    occupancy-aware argmin must split the reduction; the single-core v5e
    never pays the partials traffic for nothing."""
    m, a, b = 1 << 20, 16, 16
    bm_p, ba_p, s_p = perf_model.choose_params_tsmt(m, a, b, perf_model.V5P,
                                                    jnp.float32)
    assert s_p > 1, (bm_p, ba_p, s_p)
    # modeled time actually improves vs the sequential choice
    t_split = perf_model.tsmt_model_time(m, a, b, bm_p, ba_p,
                                         perf_model.V5P, jnp.float32,
                                         splits=s_p)
    t_seq = perf_model.tsmt_model_time(m, a, b, bm_p, ba_p, perf_model.V5P,
                                       jnp.float32, splits=1)
    assert t_split < t_seq
    *_, s_e = perf_model.choose_params_tsmt(m, a, b, perf_model.V5E,
                                            jnp.float32)
    assert s_e == 1


def test_split_partials_traffic_is_priced():
    """S = 1 must model zero partials bytes; S > 1 must cost more memory
    time at equal occupancy (same spec, parallel cells already >= cores)."""
    assert perf_model.split_partials_bytes(1, 4096, 8) == 0
    assert perf_model.split_partials_bytes(4, 4096, 8) > 0
    # m/bm = 8 parallel cells saturate even v5p's 2 cores: splitting can
    # only add partial-stack traffic, so modeled time must not improve.
    t1 = perf_model.tsm2r_model_time(2048, 2048, 8, 256, 128,
                                     perf_model.V5P, jnp.bfloat16, splits=1)
    t4 = perf_model.tsm2r_model_time(2048, 2048, 8, 256, 128,
                                     perf_model.V5P, jnp.bfloat16, splits=4)
    assert t4 >= t1


# ---------------------------------------------------------------------------
# Spec lookup by device_kind
# ---------------------------------------------------------------------------

def _device(platform, kind):
    import types
    return types.SimpleNamespace(platform=platform, device_kind=kind)


@pytest.mark.parametrize("kind,spec", [
    ("TPU v5 lite", perf_model.V5E),     # what a v5e reports
    ("TPU v5e", perf_model.V5E),
    ("TPU v5", perf_model.V5P),          # what a v5p reports
    ("TPU v5p", perf_model.V5P),
])
def test_device_spec_from_device_kind(kind, spec):
    assert perf_model.device_spec(_device("tpu", kind)) is spec


def test_device_spec_unknown_tpu_kind_raises():
    with pytest.raises(ValueError, match="TPU v9 mega"):
        perf_model.device_spec(_device("tpu", "TPU v9 mega"))


def test_device_spec_off_tpu_keeps_modelled_default():
    assert perf_model.device_spec(_device("cpu", "cpu")) is perf_model.V5E
    # the CPU test backend: the default policy models the v5e
    from repro.core import tsmm
    assert perf_model.device_spec() is perf_model.V5E
    assert tsmm.GemmPolicy().spec is perf_model.V5E


def test_policy_spec_follows_the_device(monkeypatch):
    """GemmPolicy(spec=None) resolves through device_spec at construction,
    so an unknown TPU fails loudly instead of tuning for a v5e."""
    from repro.core import tsmm
    monkeypatch.setattr(perf_model.jax, "devices",
                        lambda: [_device("tpu", "TPU v5")])
    assert tsmm.GemmPolicy().spec is perf_model.V5P
    monkeypatch.setattr(perf_model.jax, "devices",
                        lambda: [_device("tpu", "TPU v9 mega")])
    with pytest.raises(ValueError, match="no TPUSpec"):
        tsmm.GemmPolicy()
    # an explicit spec never consults the device
    assert tsmm.GemmPolicy(spec=perf_model.V5E).spec is perf_model.V5E
