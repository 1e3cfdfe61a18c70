"""The harness on the CPU at small widths: files found by name, the refusal
to run off a TPU, and ``correct`` coming out false when the timed path is
broken underneath it. ``run.run_cell`` is what ``main`` calls once it has
found the chips; these tests call it directly."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench_testlib import REPO, small_benchmark, small_cell, small_root
from bench import load, run


def _run(root, cells, cell, seed=2**31 + 77, seconds=0.3):
    return run.run_cell(small_benchmark(cells, root), cell, seed, seconds, False,
                        jax.devices()[:1], run.time.perf_counter(), root=root)


def test_new_files_of_each_kind_are_found(tmp_path):
    """A configuration, a traffic kind, a traffic mix and a metric added as
    files, with entries naming them, run without an edit to any existing
    file."""
    root = small_root(tmp_path)
    shutil.copy(root / "configs" / "rwkv6-1.6b.json", root / "configs" / "newcfg.json")
    shutil.copy(root / "configs" / "rwkv6-1.6b.py", root / "configs" / "newcfg.py")
    (root / "kinds" / "newkind.py").write_text(
        (root / "kinds" / "prefill.py").read_text().replace(
            "self.batch, self.length = ", "self.made_by = 'newkind'\n"
            "        self.batch, self.length = "))
    (root / "traffic" / "newmix.json").write_text(json.dumps(
        {"kind": "newkind", "batch": 3, "prompt_len": 8, "check_requests": 3}))
    (root / "metrics" / "new_metric.py").write_text(
        "def read(run):\n    return 42 if run.generator.made_by == 'newkind' else None\n")
    (root / "limits" / "newcfg.newmix.json").write_text('{"logit_err": 1.0}')
    cell = small_cell("newcfg", "newmix")
    bm = small_benchmark([cell], root)
    for m in bm["end_to_end"]:
        if m["name"] in ("prefill_tokens_per_s", "ttft_ms_p95"):
            m["workloads"] = [cell["name"]]
    bm["end_to_end"].append({"name": "new_metric", "unit": "x", "better": "higher",
                             "bound": 0.1, "source": "host_clock",
                             "workloads": [cell["name"]]})
    assert load.traffic("newmix", root)["batch"] == 3
    assert load.config("newcfg", root)[0]["hidden_size"] == 64
    res = run.run_cell(bm, cell, 11, 0.3, False, jax.devices()[:1],
                       run.time.perf_counter(), root=root)
    assert res["metrics"]["new_metric"]["value"] == 42
    assert {"prefill_tokens_per_s", "ttft_ms_p95", "setup_s"} <= set(res["metrics"])
    assert res["correct"] and res["attempted"] % 3 == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])
    assert list(res)[-1] == "checks"


def _bench_cmd():
    return [sys.executable, "bench/run.py", "--workload", "rwkv6-1.6b.prefill-4x1024",
            "--seed", "1", "--seconds", "1", "--trace", "0"]


def test_no_result_off_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(_bench_cmd(), cwd=REPO, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""
    assert "TPU" in p.stderr


def test_no_result_without_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ cannot run."""
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(_bench_cmd(), cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""


def _state_unchanged(monkeypatch):
    from repro.models import model
    orig = model.decode_step
    monkeypatch.setattr(model, "decode_step", lambda params, cfg, tokens, pos, cache:
                        (orig(params, cfg, tokens, pos, cache)[0], cache))


def _half_batch(monkeypatch):
    from repro.models import model
    orig = model.prefill

    def prefill(params, cfg, batch, cache):
        toks = batch["tokens"]
        half = toks[:toks.shape[0] // 2]
        return orig(params, cfg, {**batch, "tokens": jnp.concatenate([half, half])}, cache)
    monkeypatch.setattr(model, "prefill", prefill)


def _token_altered(monkeypatch):
    from repro.serve import engine
    orig = engine.sample_token
    monkeypatch.setattr(engine, "sample_token", lambda key, logits, temperature=0.0:
                        (orig(key, logits, temperature) + 1) % logits.shape[-1])


FAULTS = {
    "state_unchanged": (_state_unchanged, ["decode-small"]),
    "half_batch": (_half_batch, ["prefill-small"]),
    "token_altered": (_token_altered, ["prefill-small", "decode-small"]),
}


def _fault_root(tmp_path, traffic):
    root = small_root(tmp_path)
    spec = json.loads((root / "traffic" / f"{traffic}.json").read_text())
    spec["batch"] = 4                      # so half a batch is two requests
    if spec["kind"] == "prefill":
        spec["check_requests"] = 16
    else:
        spec["check_sequences"] = 4
    (root / "traffic" / f"{traffic}.json").write_text(json.dumps(spec))
    return root, small_cell("rwkv6-1.6b", traffic)


@pytest.mark.parametrize("traffic", ["prefill-small", "decode-small"])
def test_sound_timed_path_is_correct(tmp_path, traffic):
    root, cell = _fault_root(tmp_path, traffic)
    assert _run(root, [cell], cell)["correct"]


@pytest.mark.parametrize("fault,traffic", [(f, t) for f, (_, ts) in FAULTS.items()
                                           for t in ts])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault, traffic):
    root, cell = _fault_root(tmp_path, traffic)
    FAULTS[fault][0](monkeypatch)
    res = _run(root, [cell], cell)
    assert not res["correct"]
    assert any(c["value"] > c["limit"] for c in res["checks"].values())
