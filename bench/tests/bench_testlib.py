"""Small copies of the benchmark's configurations and traffic for CPU
tests: the same files and code paths at narrow widths."""

from __future__ import annotations

import json
import pathlib
import shutil
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (str(REPO / "src"), str(REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import load  # noqa: E402

SMALL = {
    "rwkv6-1.6b": dict(hidden_size=64, attention_hidden_size=64, head_size=16,
                       num_hidden_layers=2, intermediate_size=128,
                       time_decay_extra_dim=8, vocab_size=256),
    "zamba2-1.2b": dict(hidden_size=64, num_hidden_layers=5, mamba_headdim=16,
                        mamba_d_state=8, num_attention_heads=4,
                        num_key_value_heads=4, attention_head_dim=32,
                        intermediate_size=128, hybrid_period=2, adapter_rank=8,
                        vocab_size=256),
}
TRAFFIC = {
    "prefill-small": {"kind": "prefill", "batch": 2, "prompt_len": 16,
                      "check_requests": 4},
    "decode-small": {"kind": "decode", "batch": 2, "prompt_len": 16,
                     "max_new": 64, "check_sequences": 2},
}
# The real cell whose limit a small cell borrows.
LIMIT_OF = {"prefill-small": "prefill-4x1024", "decode-small": "decode-b8"}


def small_sizes(name: str, **extra) -> dict:
    sizes, _ = load.config(name)
    return {**sizes, **SMALL[name], **extra}


def small_root(tmp: pathlib.Path, **extra) -> pathlib.Path:
    """A benchmark tree under ``tmp`` holding the small configurations, the
    small traffic mixes, every traffic kind and metric reader, and for each
    small cell the limits of the real cell it stands for."""
    for kind in ("configs", "traffic", "kinds", "metrics", "limits"):
        (tmp / kind).mkdir(parents=True, exist_ok=True)
    for name in SMALL:
        (tmp / "configs" / f"{name}.json").write_text(json.dumps(small_sizes(name, **extra)))
        shutil.copy(load.BENCH / "configs" / f"{name}.py", tmp / "configs" / f"{name}.py")
        for t, real in LIMIT_OF.items():
            src = load.BENCH / "limits" / f"{name}.{real}.json"
            if not src.is_file():
                src = load.BENCH / "limits" / f"rwkv6-1.6b.{real}.json"
            shutil.copy(src, tmp / "limits" / f"{name}.{t}.json")
    for t, spec in TRAFFIC.items():
        (tmp / "traffic" / f"{t}.json").write_text(json.dumps(spec))
    for sub in ("kinds", "metrics"):
        for m in (load.BENCH / sub).glob("*.py"):
            shutil.copy(m, tmp / sub / m.name)
    return tmp


def small_cell(config: str, traffic: str) -> dict:
    return {"name": f"{config}.{traffic}", "config": config, "traffic": traffic,
            "chips": 1}


def small_benchmark(cells: list[dict], root: pathlib.Path) -> dict:
    """BENCHMARK.json's metric entries, re-pointed at the small cells by
    the kind of their traffic."""
    bm = load.benchmark()
    kinds = {c["name"]: load.traffic(c["traffic"], root)["kind"] for c in cells}
    real = {w["name"]: load.traffic(w["traffic"])["kind"] for w in bm["workloads"]}

    def repoint(entry):
        entry = dict(entry)
        if "workloads" in entry:
            want = {real[w] for w in entry["workloads"]}
            entry["workloads"] = [n for n, k in kinds.items() if k in want]
        return entry
    return {**bm, "workloads": cells,
            "end_to_end": [repoint(m) for m in bm["end_to_end"]],
            "per_layer": [repoint(m) for m in bm["per_layer"]]}
