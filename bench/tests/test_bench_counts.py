"""The peaks table and the configurations' FLOP and byte counts against a
hand count at one small shape (``bench_testlib.SMALL``)."""

from __future__ import annotations

import jax
import pytest

from bench_testlib import small_sizes
from bench import load, peaks


def test_peaks_table():
    p = peaks.peak("TPU v5 lite")
    assert (p.bf16_flops, p.int8_ops, p.hbm_bytes_per_s) == (197e12, 393e12, 819e9)
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v7x")
    with pytest.raises(KeyError):
        peaks.compute_peak(p, "f32")
    # 1 GFLOP and 1 GB: 1e9 / 819e9 s of memory beats 1e9 / 197e12 s of compute.
    t, bound = peaks.least_time_s(1e9, 1e9, p, "bf16")
    assert (t, bound) == (pytest.approx(1e9 / 819e9), "memory")
    t, bound = peaks.least_time_s(1e12, 1e6, p, "s8")
    assert (t, bound) == (pytest.approx(1e12 / 393e12), "compute")


def _params_bytes(mod, sizes) -> int:
    shapes = jax.eval_shape(lambda k: mod.make_params(k, sizes), jax.random.PRNGKey(0))
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))


def test_rwkv6_counts_by_hand():
    s = small_sizes("rwkv6-1.6b")     # d 64, ff 128, LoRA 8, 4 heads of 16, 2 layers, V 256
    _, mod = load.config("rwkv6-1.6b")
    # per token per layer: 5 d^2 + 2 d r + 2 d ff + d^2 = 41984 multiply-adds,
    # x 2 FLOPs, + 7 x 4 x 16^2 = 7168 for the recurrence: 91136; 2 layers: 182272.
    # head: 2 x 64 x 256 = 32768 per position whose logits are computed.
    assert mod.prefill_flops(s, 2, 8) == 2 * 8 * 182272 + 2 * 32768
    assert mod.decode_flops(s, 3, 100) == 3 * (182272 + 32768)
    pb = _params_bytes(mod, s)
    # state per layer per sequence: WKV 4 x 16 x 16 f32 (4096 B) + two bf16
    # rows of 64 (256 B); read and written; 2 layers x 2 sequences.
    want = pb - 256 * 64 * 2 + 2 * 64 * 2 + 2 * (2 * 2 * 4352)
    assert mod.decode_bytes(s, pb, 2, 50) == want


def test_zamba2_counts_by_hand():
    s = small_sizes("zamba2-1.2b")    # d 64, 5 layers, d_inner 128 (8 heads of 16),
    _, mod = load.config("zamba2-1.2b")   # N 8, 4 attention heads of 32, ff 128, period 2, r 8
    # Mamba2 per token: in 64 x 280 + out 128 x 64 = 26112 MACs -> 52224,
    # conv 2 x 4 x 144 = 1152, recurrence 5 x 8 x 8 x 16 = 5120: 58496; 5 layers.
    mamba = 5 * 58496
    # shared block per application: qkv 64 x 12 x 32 + out 128 x 64 + MLP 3 x 64 x 128
    # + two LoRAs 2 x 2 x 64 x 8 = 59392 MACs -> 118784; applied 2 times (5 // 2).
    shared = 2 * 118784
    # attention: 4 x 4 x 32 = 512 FLOPs per key; causal prefill of 8: 36 keys.
    attn_prefill = 2 * 512 * 36
    head = 2 * 64 * 256
    assert mod.prefill_flops(s, 2, 8) == 2 * (8 * (mamba + shared) + attn_prefill + head)
    assert mod.decode_flops(s, 1, 9) == mamba + shared + 2 * 512 * 10 + head
    # Both LoRA pairs of every application are parameters of the model.
    shapes = jax.eval_shape(lambda k: mod.make_params(k, s), jax.random.PRNGKey(0))
    seg = shapes["segments"][0]
    assert sum(x.size for x in jax.tree.leaves([seg["lora_attn"], seg["lora_ffn"]])) \
        == 2 * 2 * (64 * 8 + 8 * 64)
    pb = _params_bytes(mod, s)
    # Mamba2 state per layer per sequence: 8 x 8 x 16 f32 (4096 B) + conv rows
    # 3 x 144 bf16 (864 B); KV per application: positions 0..pos read + one
    # slot written, 2 x 4 x 32 bf16 (512 B) each.
    want = (pb - 256 * 64 * 2 + 1 * 64 * 2 + 2 * 5 * 1 * 4960
            + 2 * 1 * (9 + 2) * 512)
    assert mod.decode_bytes(s, pb, 1, 9) == want
