"""Architecture registry: ``--arch <id>`` resolves here.

``ARCHS``/``SMOKES`` map the architecture ids to their exact published
configs (and kanana's one-chip expert share, ``-ep16``) and to reduced
same-family smoke configs. ``DP_MODE``
records the production data-axis policy per arch (see DESIGN.md §3.5/§8):

  'dp'   — parameters replicated over the data axis; gs-SGD compresses the
           gradient all-reduce over ALL data-parallel axes (paper-faithful).
  'fsdp' — parameters/optimizer-state sharded over the in-pod data axis
           (ZeRO-3; needed where replicated state exceeds HBM); the in-pod
           reduce is fused into backward, and gs-SGD compresses the
           *cross-pod* gradient exchange — the slow axis, which is exactly
           the low-bandwidth link the paper targets.
"""

from __future__ import annotations

from repro.configs import shapes
from repro.configs.granite_moe_3b_a800m import CONFIG as _granite
from repro.configs.granite_moe_3b_a800m import SMOKE as _granite_s
from repro.configs.kanana_2_30b_a3b import CONFIG as _kanana
from repro.configs.kanana_2_30b_a3b import EP16 as _kanana_ep16
from repro.configs.kanana_2_30b_a3b import SMOKE as _kanana_s
from repro.configs.llama_3_2_vision_11b import CONFIG as _llava
from repro.configs.llama_3_2_vision_11b import SMOKE as _llava_s
from repro.configs.minicpm_2b import CONFIG as _minicpm
from repro.configs.minicpm_2b import SMOKE as _minicpm_s
from repro.configs.musicgen_large import CONFIG as _musicgen
from repro.configs.musicgen_large import SMOKE as _musicgen_s
from repro.configs.qwen3_4b import CONFIG as _qwen3
from repro.configs.qwen3_4b import SMOKE as _qwen3_s
from repro.configs.qwen3_moe_235b_a22b import CONFIG as _qwen3moe
from repro.configs.qwen3_moe_235b_a22b import SMOKE as _qwen3moe_s
from repro.configs.rwkv6_7b import CONFIG as _rwkv6
from repro.configs.rwkv6_7b import SMOKE as _rwkv6_s
from repro.configs.starcoder2_3b import CONFIG as _starcoder2
from repro.configs.starcoder2_3b import SMOKE as _starcoder2_s
from repro.configs.yi_9b import CONFIG as _yi
from repro.configs.yi_9b import SMOKE as _yi_s
from repro.configs.zamba2_2_7b import CONFIG as _zamba2
from repro.configs.zamba2_2_7b import SMOKE as _zamba2_s
from repro.models.common import ArchConfig

ARCHS: dict[str, ArchConfig] = {
    "llama-3.2-vision-11b": _llava,
    "qwen3-moe-235b-a22b": _qwen3moe,
    "granite-moe-3b-a800m": _granite,
    "qwen3-4b": _qwen3,
    "yi-9b": _yi,
    "minicpm-2b": _minicpm,
    "starcoder2-3b": _starcoder2,
    "rwkv6-7b": _rwkv6,
    "musicgen-large": _musicgen,
    "zamba2-2.7b": _zamba2,
    "kanana-2-30b-a3b": _kanana,
    "kanana-2-30b-a3b-ep16": _kanana_ep16,
}

SMOKES: dict[str, ArchConfig] = {
    "llama-3.2-vision-11b": _llava_s,
    "qwen3-moe-235b-a22b": _qwen3moe_s,
    "granite-moe-3b-a800m": _granite_s,
    "qwen3-4b": _qwen3_s,
    "yi-9b": _yi_s,
    "minicpm-2b": _minicpm_s,
    "starcoder2-3b": _starcoder2_s,
    "rwkv6-7b": _rwkv6_s,
    "musicgen-large": _musicgen_s,
    "zamba2-2.7b": _zamba2_s,
    "kanana-2-30b-a3b": _kanana_s,
}

# Production data-axis policy (HBM-driven; see module docstring).
DP_MODE: dict[str, str] = {
    "llama-3.2-vision-11b": "fsdp",   # ~10.7B params
    "qwen3-moe-235b-a22b": "fsdp",    # ~235B params
    "granite-moe-3b-a800m": "dp",     # ~3.4B
    "qwen3-4b": "dp",                 # ~4.0B
    "yi-9b": "fsdp",                  # ~8.8B
    "minicpm-2b": "dp",               # ~2.7B
    "starcoder2-3b": "dp",            # ~3.0B
    "rwkv6-7b": "fsdp",               # ~7.6B
    "musicgen-large": "dp",           # ~3.3B
    "zamba2-2.7b": "dp",              # ~2.7B
    "kanana-2-30b-a3b": "fsdp",       # ~30.7B
    "kanana-2-30b-a3b-ep16": "dp",    # one chip's share, ~0.43B at 5 layers
}


# Per-arch training overrides for the production lowering. qwen3-moe-235b
# runs the paper's own optimizer (SGD+momentum, 1 state slot) with a bf16
# error-feedback accumulator: at 235B params / 512 chips the AdamW + f32-EF
# state would exceed v5e HBM (see DESIGN.md §8 memory budget table).
TRAIN_OVERRIDES: dict[str, dict] = {
    "qwen3-moe-235b-a22b": {"optimizer": "sgdm", "ef_dtype": "bfloat16",
                            "microbatch": 2},
}


def get(name: str) -> ArchConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(ARCHS)}")
    return ARCHS[name]


def get_smoke(name: str) -> ArchConfig:
    return SMOKES[name]


__all__ = ["ARCHS", "SMOKES", "DP_MODE", "TRAIN_OVERRIDES", "get",
           "get_smoke", "shapes"]
