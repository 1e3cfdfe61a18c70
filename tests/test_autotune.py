"""Autotuner subsystem: TuningTable round-trip + key stability, the
``GemmPolicy.tuning_table`` override of the analytic block choice
(asserted via a kernel-kwargs spy), measured autotuning + calibration on
synthetic timings, and an interpret-mode smoke of ``benchmarks.run
--autotune``.
"""

import dataclasses
import importlib
import json
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import autotune, perf_model, tsmm
from repro.kernels import ops, ref


def _rand(seed, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype)


def _record(kind="tsm2r", shape=(4096, 1024, 8), dtype="float32",
            spec="tpu_v5e", executor="interpret", params=None,
            model_pick=None):
    params = params or {"block_m": 256, "block_k": 128}
    return autotune.TuningRecord(
        kind=kind, bucket=autotune.bucket_shape(*shape), dtype=dtype,
        spec_name=spec, executor=executor, shape=shape,
        params=tuple(sorted(params.items())), measured_us=120.0,
        model_us=100.0, model_error=0.2,
        model_pick=tuple(sorted((model_pick or params).items())),
        model_pick_measured_us=150.0)


# ---------------------------------------------------------------------------
# Bucketing + keys
# ---------------------------------------------------------------------------

def test_bucket_dim_scheme():
    # <= one lane tile: exact (skinny dims flip kernel choice sharply)
    assert [autotune.bucket_dim(d) for d in (1, 8, 100, 128)] == [1, 8, 100, 128]
    # above: next power of two
    assert autotune.bucket_dim(129) == 256
    assert autotune.bucket_dim(4096) == 4096
    assert autotune.bucket_dim(20480) == 32768


def test_record_key_stability():
    """The on-disk key format is an API: loaders from other processes /
    commits must produce identical keys for identical cells."""
    key = autotune.record_key("tsm2r", autotune.bucket_shape(20480, 20480, 16),
                              "bfloat16", "tpu_v5e", "pallas-tpu")
    assert key == "tsm2r|32768x32768x16|bfloat16|tpu_v5e|pallas-tpu"
    assert _record().key == "tsm2r|4096x1024x8|float32|tpu_v5e|interpret"


def test_table_roundtrip_and_lookup(tmp_path):
    rec = _record()
    tbl = autotune.TuningTable.from_records([rec])
    path = tmp_path / "table.json"
    tbl.save(path)
    data = json.loads(path.read_text())
    assert data["schema"] == autotune.TABLE_SCHEMA
    assert data["records"][0]["key"] == rec.key
    loaded = autotune.TuningTable.load(path)
    assert loaded == tbl
    # lookup buckets the query shape: any shape in the bucket hits.
    hit = loaded.lookup("tsm2r", 3000, 1000, 8, dtype=jnp.float32,
                        spec="tpu_v5e", executor="interpret")
    assert hit == rec and hit.params_dict == {"block_m": 256, "block_k": 128}
    assert loaded.lookup("tsm2r", 3000, 1000, 16, dtype=jnp.float32,
                         spec="tpu_v5e", executor="interpret") is None
    assert loaded.lookup("tsm2r", 3000, 1000, 8, dtype=jnp.float32,
                         spec="tpu_v5e", executor="pallas-tpu") is None


def test_table_add_replaces_same_key():
    tbl = autotune.TuningTable.from_records([_record()])
    newer = _record(params={"block_m": 512, "block_k": 256})
    tbl2 = tbl.add(newer)
    assert len(tbl2.records) == 1
    assert tbl2.records[0].params_dict == {"block_m": 512, "block_k": 256}
    assert len(tbl.records) == 1  # original untouched (immutable)


def test_table_is_hashable_on_policy():
    """The table rides through custom_vjp nondiff args on the policy."""
    tbl = autotune.TuningTable.from_records([_record()])
    pol = tsmm.GemmPolicy(tuning_table=tbl)
    assert hash(pol) == hash(tsmm.GemmPolicy(tuning_table=tbl))
    assert pol != tsmm.GemmPolicy()


def test_from_json_rejects_foreign_schema():
    with pytest.raises(ValueError, match="not a tuning table"):
        autotune.TuningTable.from_json({"schema": "repro-tsm2x-bench/1",
                                        "records": []})


# ---------------------------------------------------------------------------
# tuning_table overrides the analytic choice (acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.fixture
def tsm2r_spy(monkeypatch):
    seen = []
    orig = ops.tsm2r_pallas

    def spy(a, b, *, block_m, block_k, interpret, vmem_limit_bytes):
        seen.append({"block_m": block_m, "block_k": block_k})
        return orig(a, b, block_m=block_m, block_k=block_k,
                    interpret=interpret,
                    vmem_limit_bytes=vmem_limit_bytes)

    monkeypatch.setattr(ops, "tsm2r_pallas", spy)
    return seen


def test_tuning_table_overrides_analytic_choice(tsm2r_spy):
    m, k, n = 4096, 1024, 8
    a, b = _rand(0, (m, k)), _rand(1, (k, n))
    analytic = perf_model.choose_params_tsm2r(m, k, n, perf_model.V5E,
                                              a.dtype)
    tuned = {"block_m": 256, "block_k": 128}
    assert tuned != dict(zip(("block_m", "block_k"), analytic))
    tbl = autotune.TuningTable.from_records(
        [_record(shape=(m, k, n), params=tuned)])

    with tsmm.policy(tuning_table=tbl, interpret=True):
        got = tsmm.tsmm(a, b)
    assert tsm2r_spy[-1] == tuned
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.tsm2r_ref(a, b)),
                               rtol=1e-4, atol=1e-4)

    # same call without the table: analytic params, same numerics.
    with tsmm.policy(interpret=True):
        tsmm.tsmm(a, b)
    assert tuple(tsm2r_spy[-1].values()) == analytic[:2]


def test_explicit_block_kwargs_beat_table(tsm2r_spy):
    m, k, n = 4096, 1024, 8
    a, b = _rand(2, (m, k)), _rand(3, (k, n))
    tbl = autotune.TuningTable.from_records(
        [_record(shape=(m, k, n), params={"block_m": 256, "block_k": 128})])
    with tsmm.policy(tuning_table=tbl, interpret=True):
        ops.tsm2r(a, b, block_m=512, block_k=256)
    assert tsm2r_spy[-1] == {"block_m": 512, "block_k": 256}


def test_table_miss_on_other_executor_falls_back(tsm2r_spy):
    """A table tuned for pallas-tpu must not drive interpret-mode calls."""
    m, k, n = 4096, 1024, 8
    a, b = _rand(4, (m, k)), _rand(5, (k, n))
    tbl = autotune.TuningTable.from_records(
        [_record(shape=(m, k, n), executor="pallas-tpu",
                 params={"block_m": 256, "block_k": 128})])
    analytic = perf_model.choose_params_tsm2r(m, k, n, perf_model.V5E,
                                              a.dtype)
    with tsmm.policy(tuning_table=tbl, interpret=True):
        tsmm.tsmm(a, b)
    assert tuple(tsm2r_spy[-1].values()) == analytic[:2]


# ---------------------------------------------------------------------------
# Schema back-compat (v1 tables: no "splits" param, no "fits" block)
# ---------------------------------------------------------------------------

def _v1_payload(m=4096, k=1024, n=8):
    return {
        "schema": "repro-tsm2x-tuning/1",
        "records": [{
            "key": "ignored-on-load",
            "kind": "tsm2r", "bucket": [m, k, n], "dtype": "float32",
            "spec": "tpu_v5e", "executor": "interpret", "shape": [m, k, n],
            "params": {"block_m": 256, "block_k": 128},
            "measured_us": 10.0, "model_us": 9.0, "model_error": 0.1,
            "model_pick": {"block_m": 256, "block_k": 128},
            "model_pick_measured_us": 10.0,
        }],
    }


def test_v1_table_loads_and_defaults_to_sequential(tsm2r_spy):
    """Pre-split tables (schema /1) must keep loading; their records carry
    no "splits" key, so consumption runs the sequential kernel they
    actually measured -- and fitted_spec is the identity."""
    tbl = autotune.TuningTable.from_json(_v1_payload())
    rec = tbl.lookup("tsm2r", 4096, 1024, 8, dtype=jnp.float32,
                     spec="tpu_v5e", executor="interpret")
    assert rec is not None and "splits" not in rec.params_dict
    assert tbl.fitted_spec("tsm2r", 4096, 1024, 8, dtype=jnp.float32,
                           spec=perf_model.V5E) == perf_model.V5E
    a, b = _rand(10, (4096, 1024)), _rand(11, (1024, 8))
    with tsmm.policy(tuning_table=tbl, interpret=True):
        got = tsmm.tsmm(a, b)
    assert tsm2r_spy[-1] == {"block_m": 256, "block_k": 128}  # sequential
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.tsm2r_ref(a, b)),
                               rtol=1e-4, atol=1e-4)


def test_table_roundtrips_splits_and_fits(tmp_path):
    """The v2 additions survive save/load: splits in record params, the
    per-bucket + global fits block."""
    rec = _record(params={"block_m": 256, "block_k": 128, "splits": 4})
    fits = (
        autotune.SpecFit("tsm2r", autotune.bucket_shape(4096, 1024, 8),
                         "float32", "tpu_v5e", 1e-6, 2e-6,
                         vmem_usable=0.75),
        autotune.SpecFit(*autotune.GLOBAL_FIT, "tpu_v5e", 3e-7, 1.5e-6),
    )
    tbl = autotune.TuningTable.from_records([rec], fits)
    path = tmp_path / "v2.json"
    tbl.save(path)
    loaded = autotune.TuningTable.load(path)
    assert loaded == tbl
    hit = loaded.lookup("tsm2r", 4096, 1024, 8, dtype=jnp.float32,
                        spec="tpu_v5e", executor="interpret")
    assert hit.params_dict["splits"] == 4
    # bucket-local fit wins over the global cell; off-bucket gets global
    local = loaded.fitted_spec("tsm2r", 4096, 1024, 8, dtype=jnp.float32,
                               spec=perf_model.V5E)
    assert (local.step_overhead, local.dma_latency) == (1e-6, 2e-6)
    # the fitted vmem budget rides along (and only ever widens)
    assert local.vmem_usable == 0.75
    other = loaded.fitted_spec("tsmt", 65536, 64, 8, dtype=jnp.float32,
                               spec=perf_model.V5E)
    assert (other.step_overhead, other.dma_latency) == (3e-7, 1.5e-6)
    # the global cell carries no vmem correction: budget untouched
    assert other.vmem_usable == perf_model.V5E.vmem_usable


def test_bucket_fit_drives_analytic_choice(tsm2r_spy):
    """A table with NO record for the bucket but a bucket-local fit must
    run the analytic chooser under the fitted constants: a zero-latency
    fit flips the tsm2r tie-break to the deepest k-pipeline (bk=128),
    which the stock V5E constants would never pick for this shape."""
    m, k, n = 4096, 1024, 8
    stock = perf_model.choose_params_tsm2r(m, k, n, perf_model.V5E,
                                           jnp.float32)
    fit = autotune.SpecFit("tsm2r", autotune.bucket_shape(m, k, n),
                           "float32", "tpu_v5e", 0.0, 0.0)
    tbl = autotune.TuningTable.from_records([], [fit])
    a, b = _rand(12, (m, k)), _rand(13, (k, n))
    with tsmm.policy(tuning_table=tbl, interpret=True):
        tsmm.tsmm(a, b)
    assert tsm2r_spy[-1]["block_k"] == 128 != stock[1]


def test_calibrate_populates_per_bucket_fits():
    pol = tsmm.GemmPolicy(interpret=True)
    res = autotune.calibrate([("tsm2r", 1024, 256, 8), ("tsmt", 1024, 64, 8)],
                             dtype=jnp.float32, policy=pol, reps=1, warmup=0)
    fits = {(f.kind, f.bucket) for f in res.table.fits}
    assert ("*", (0, 0, 0)) in fits              # the global cell
    assert ("tsm2r", autotune.bucket_shape(1024, 256, 8)) in fits
    assert ("tsmt", autotune.bucket_shape(1024, 64, 8)) in fits
    # the table stays policy-hashable with fits attached
    assert hash(tsmm.GemmPolicy(tuning_table=res.table)) is not None


# ---------------------------------------------------------------------------
# Measured autotuning (interpret mode, tiny shapes)
# ---------------------------------------------------------------------------

def test_autotune_shape_produces_consistent_record():
    pol = tsmm.GemmPolicy(interpret=True)
    rec = autotune.autotune_shape("tsm2r", 1024, 256, 8, dtype=jnp.float32,
                                  policy=pol, reps=1, warmup=0)
    assert rec.kind == "tsm2r" and rec.executor == "interpret"
    assert rec.shape == (1024, 256, 8)
    cands = perf_model.tsm2r_candidates(1024, 256, 8, pol.spec, jnp.float32)
    assert tuple(rec.params_dict[k]
                 for k in ("block_m", "block_k", "splits")) in cands
    assert rec.measured_us > 0 and rec.model_error >= 0
    assert rec.model_pick_measured_us > 0  # the analytic pick was timed too
    tbl = autotune.TuningTable.from_records([rec])
    assert tbl.lookup("tsm2r", 1024, 256, 8, dtype=jnp.float32,
                      spec=pol.spec.name, executor="interpret") == rec


def test_autotune_unknown_kind_raises():
    with pytest.raises(ValueError, match="unknown kernel kind"):
        autotune.autotune_shape("tsmr", 1024, 256, 8)


def test_explore_vmem_widens_the_measured_search():
    """The measured search must be able to probe past the model's VMEM
    feasibility filter -- otherwise a model-pruned winner can never be
    observed and fit_spec's vmem_usable correction is unreachable."""
    tight = dataclasses.replace(perf_model.V5E, vmem_usable=0.02)
    strict, _, pick = autotune._kind_plan("tsm2r", 8192, 4096, 8, tight,
                                          jnp.bfloat16)
    explored, _, _ = autotune._kind_plan("tsm2r", 8192, 4096, 8, tight,
                                         jnp.bfloat16, explore_vmem=4.0)
    assert set(map(tuple, (c.items() for c in strict))) < \
        set(map(tuple, (c.items() for c in explored)))
    budget = tight.vmem_bytes * tight.vmem_usable
    over = [c for c in explored
            if perf_model.tsm2r_vmem_usage(c["block_m"], c["block_k"], 8,
                                           jnp.bfloat16) > budget]
    assert over, "explored set must contain strictly-over-budget configs"
    assert pick in strict or strict == []


def test_build_table_warns_on_bucket_collision():
    pol = tsmm.GemmPolicy(interpret=True)
    with pytest.warns(UserWarning, match="share table bucket"):
        tbl = autotune.build_table(
            [("tsm2r", 2000, 512, 8), ("tsm2r", 1500, 512, 8)],
            dtype=jnp.float32, policy=pol, reps=1, warmup=0)
    assert len(tbl.records) == 1  # merged: the faster winner survives


# ---------------------------------------------------------------------------
# Calibration
# ---------------------------------------------------------------------------

def _synthetic_observations(true_spec):
    obs = []
    for m, k, n, bm, bk in [(4096, 4096, 8, 256, 128),
                            (4096, 4096, 8, 1024, 512),
                            (8192, 2048, 16, 512, 128),
                            (2048, 2048, 8, 256, 256)]:
        t = perf_model.tsm2r_model_time(m, k, n, bm, bk, true_spec,
                                        jnp.bfloat16)
        obs.append(autotune.Observation(
            "tsm2r", m, k, n, "bfloat16",
            (("block_k", bk), ("block_m", bm)), t))
    for m, bm in [(1_000_000, 256), (1_000_000, 4096)]:
        t = perf_model.tsm2l_model_time(m, 16, 16, bm, true_spec, jnp.bfloat16)
        obs.append(autotune.Observation("tsm2l", m, 16, 16, "bfloat16",
                                        (("block_m", bm),), t))
    return obs


def test_calibrate_reduces_model_error_on_synthetic_timings():
    """Timings generated from a spec with 8x step overhead / 4x DMA latency:
    fitting must recover the scales and collapse the error."""
    true_spec = dataclasses.replace(perf_model.V5E,
                                    step_overhead=perf_model.V5E.step_overhead * 8,
                                    dma_latency=perf_model.V5E.dma_latency * 4)
    obs = _synthetic_observations(true_spec)
    result = autotune.fit_spec(perf_model.V5E, obs)
    assert result.error_before > 0.05
    assert result.error_after < result.error_before * 0.2
    assert result.spec.step_overhead > perf_model.V5E.step_overhead
    assert result.spec.dma_latency > perf_model.V5E.dma_latency


def test_fit_spec_raises_vmem_usable_for_measured_winners():
    """A measured winner the modeled budget would have pruned proves the
    budget too conservative: vmem_usable is raised minimally to admit it."""
    tight = dataclasses.replace(perf_model.V5E, vmem_usable=0.01)
    obs = [autotune.Observation(
        "tsm2r", 8192, 8192, 8, "bfloat16",
        (("block_k", 2048), ("block_m", 4096)),
        perf_model.tsm2r_model_time(8192, 8192, 8, 4096, 2048))]
    need = obs[0].vmem_bytes() / tight.vmem_bytes
    result = autotune.fit_spec(tight, obs, fit=())
    assert result.spec.vmem_usable == pytest.approx(need)


def test_fit_spec_empty_observations_is_identity():
    result = autotune.fit_spec(perf_model.V5E, [])
    assert result.spec == perf_model.V5E
    assert result.error_before == result.error_after == 0.0


def test_calibrate_base_table_merges_records_and_ages_out_fits():
    """Partial re-calibration: ``calibrate(base_table=...)`` keeps base
    records (new measurements win shared buckets) but drops the base's
    SpecFit cells -- stale fitted constants from an older run must not
    keep steering the analytic chooser."""
    pol = tsmm.GemmPolicy(interpret=True)
    base = autotune.calibrate([("tsm2r", 1024, 256, 8),
                               ("tsm2l", 1024, 16, 16)],
                              dtype=jnp.float32, policy=pol,
                              reps=1, warmup=0).table
    # poison one base fit so survival would be observable
    stale = autotune.SpecFit("tsm2l", autotune.bucket_shape(1024, 16, 16),
                             "float32", pol.spec.name,
                             step_overhead=123.0, dma_latency=456.0)
    base = autotune.TuningTable(records=base.records, fits=(stale,))

    res = autotune.calibrate([("tsm2r", 1024, 256, 8)], dtype=jnp.float32,
                             policy=pol, reps=1, warmup=0, base_table=base)
    keys = {r.key for r in res.table.records}
    # the un-remeasured base record survives; the shared bucket is replaced
    assert any(k.startswith("tsm2l|") for k in keys)
    assert any(k.startswith("tsm2r|") for k in keys)
    new_rec = next(r for r in res.table.records if r.kind == "tsm2r")
    assert new_rec.shape == (1024, 256, 8)
    # every fit comes from THIS run: the poisoned tsm2l cell is gone
    assert all(f.step_overhead != 123.0 for f in res.table.fits)
    fit_kinds = {f.kind for f in res.table.fits}
    assert "tsm2l" not in fit_kinds and "tsm2r" in fit_kinds
    assert ("*", (0, 0, 0)) in {(f.kind, f.bucket) for f in res.table.fits}


# ---------------------------------------------------------------------------
# benchmarks.run --autotune smoke (interpret mode)
# ---------------------------------------------------------------------------

def _import_bench_run():
    root = pathlib.Path(__file__).resolve().parents[1]
    sys.path.insert(0, str(root))
    try:
        return importlib.import_module("benchmarks.run")
    finally:
        sys.path.remove(str(root))


def test_run_autotune_smoke(tmp_path):
    run_mod = _import_bench_run()
    out = tmp_path / "BENCH_smoke.json"
    run_mod.main(["--json", str(out), "--autotune",
                  "--autotune-shapes", "tsm2r:1024,256,8",
                  "--sections", "Table3/4"])
    report = json.loads(out.read_text())
    at = report["autotune"]
    assert at["table"]["records"], "autotune table must not be empty"
    assert at["model_error"] and all("model_error" in e
                                     for e in at["model_error"])
    assert {"error_before", "error_after", "fitted"} <= set(at["calibration"])
    sanity = report["dispatch_sanity"]
    assert sanity and all(s["ok"] for s in sanity)
    # the tuned table round-trips through the public loader
    tbl = autotune.TuningTable.from_json(at["table"])
    assert tbl.lookup("tsm2r", 1024, 256, 8, dtype=jnp.float32,
                      spec="tpu_v5e", executor="interpret") is not None


def test_parse_autotune_shapes_errors():
    run_mod = _import_bench_run()
    assert run_mod.parse_autotune_shapes("tsm2r:4096,1024,8;tsm2l:8192,16,16") \
        == [("tsm2r", 4096, 1024, 8), ("tsm2l", 8192, 16, 16)]
    with pytest.raises(SystemExit):
        run_mod.parse_autotune_shapes("tsm2r:oops")
