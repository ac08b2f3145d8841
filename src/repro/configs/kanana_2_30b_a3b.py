"""kanana-2-30b-a3b [moe] — 48L d_model=2048, MLA 32H (no query latent,
kv latent 512, qk 128 + 64 rotary, v 128), 1 leading dense layer
(d_ff=6144) then 47 MoE layers of 128 routed experts (width 768, top-6,
sigmoid scores, noaux_tc selection bias, gates normalised and scaled by
2.448) + 2 shared experts, vocab=128256, untied
[hf:kakaocorp/kanana-2-30b-a3b-instruct-2601, model_type deepseek_v3].

``EP16`` is one chip's share of the 16-chip expert-parallel deployment the
benchmark states: each MoE layer's 128 experts over 16 chips (8 held
here; every chip routes every token over all 128 and computes its 8), the
vocabulary split 8 ways (16032 of 128256 rows here). Depth is cut by the
run (``RunSpec.layers``), the leading dense layer kept.
"""

import dataclasses

from repro.models.common import ArchConfig

CONFIG = ArchConfig(
    name="kanana-2-30b-a3b",
    family="moe",
    n_layers=48,
    d_model=2048,
    n_heads=32,
    n_kv_heads=32,
    d_ff=6144,
    vocab_size=128256,
    rope_theta=1000000.0,
    norm_eps=1e-6,
    kv_lora_rank=512,
    qk_nope_dim=128,
    qk_rope_dim=64,
    v_head_dim=128,
    n_experts=128,
    experts_per_tok=6,
    moe_d_ff=768,
    n_shared_experts=2,
    router="sigmoid",
    routed_scale=2.448,
    bias_rate=0.001,
    first_dense=1,
    block="moe",
    max_seq_len=32768,
    notes="MLA + 128 experts top-6 (sigmoid, noaux_tc) + 2 shared; "
          "EP over the model axis",
)

EP16 = dataclasses.replace(CONFIG, name="kanana-2-30b-a3b-ep16",
                           experts_held=8, vocab_size=16032)

SMOKE = dataclasses.replace(
    CONFIG, name="kanana-2-30b-a3b-smoke", n_layers=3, d_model=64,
    n_heads=4, n_kv_heads=4, d_ff=96, vocab_size=256, kv_lora_rank=16,
    qk_nope_dim=8, qk_rope_dim=8, v_head_dim=8, n_experts=16,
    experts_per_tok=3, moe_d_ff=16, experts_held=4, max_seq_len=8192)
