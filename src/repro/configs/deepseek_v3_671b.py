"""deepseek-v3-671b [moe]: MLA + 1 shared / 256 routed top-8 experts.

61L d_model=7168 128H d_ff(expert)=2048 vocab=129280 [arXiv:2412.19437;
huggingface.co/deepseek-ai/DeepSeek-V3 config.json]. First 3 layers dense
(d_ff=18432); sigmoid router with aux-loss-free bias and group-limited
(noaux_tc) selection, 8 groups of which 4 are kept; routed output scaled
2.5. MLA: q_lora 1536, kv_lora 512, rope 64 with YaRN (factor 40 over 4096
original positions, softmax scale times mscale^2); RMSNorm eps 1e-6 -- the
low-rank projections are TSM2X dispatch shapes.

``chip_share`` is one expert-parallel rank of the published prefill
deployment (arXiv:2412.19437 §3.4.1: EP32 for the experts), cut in depth:
the configuration the benchmark's ``deepseek-v3-671b`` cell runs.

MTP (multi-token prediction) is NOT implemented (noted in PERF.md): serving
does not use it, and it adds an auxiliary loss head orthogonal to this
paper's kernel/runtime focus.
"""

import dataclasses

from repro.configs.base import MLAConfig, ModelConfig
from repro.models.layers import YaRN
from repro.models.moe import MoEConfig

CONFIG = ModelConfig(
    name="deepseek-v3-671b", family="moe",
    n_layers=61, d_model=7168, n_heads=128, n_kv_heads=128, d_ff=18432,
    vocab_size=129280, head_dim=128, norm_eps=1e-6,
    mla=MLAConfig(q_lora=1536, kv_lora=512, nope_dim=128, rope_dim=64,
                  v_dim=128, yarn=YaRN(factor=40.0, original_max_pos=4096)),
    moe=MoEConfig(n_experts=256, top_k=8, d_ff_expert=2048, n_shared=1,
                  d_ff_shared=2048, router="sigmoid", routed_scale=2.5,
                  n_group=8, topk_group=4),
    first_k_dense=3,
    dtype="bfloat16", microbatch=4,
)

EP_RANKS = 32   # chips that share each layer's experts in the prefill deployment


def chip_share(rank: int = 0, n_layers: int = 5, first_k_dense: int = 1) -> ModelConfig:
    """Expert-parallel rank ``rank`` of ``EP_RANKS``: it holds experts
    [8 rank, 8 rank + 8) of each layer's 256 and routes over all of them.
    The leading dense layers count once and ``n_layers - first_k_dense``
    MoE layers follow; every width is as published."""
    per = CONFIG.moe.n_experts // EP_RANKS
    return dataclasses.replace(
        CONFIG, n_layers=n_layers, first_k_dense=first_k_dense,
        moe=dataclasses.replace(CONFIG.moe, first_held=rank * per, n_held=per))


def smoke() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v3-smoke", family="moe",
        n_layers=3, d_model=64, n_heads=4, n_kv_heads=4, d_ff=160,
        vocab_size=256, head_dim=16, norm_eps=1e-6,
        mla=MLAConfig(q_lora=32, kv_lora=16, nope_dim=16, rope_dim=8, v_dim=16,
                      yarn=YaRN(factor=40.0, original_max_pos=4096)),
        moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=32, n_shared=1,
                      d_ff_shared=32, router="sigmoid", routed_scale=2.5,
                      n_group=4, topk_group=2),
        first_k_dense=1,
        q_chunk=16, kv_chunk=16, dtype="float32",
    )
