"""Assigned input-shape set (identical for all ten LM-family architectures).

``train_4k`` lowers ``train_step``; ``prefill_32k`` lowers the prompt
forward; ``decode_32k`` / ``long_500k`` lower ``serve_step`` (one new token
against a ``seq_len``-long KV cache / recurrent state). ``long_500k``
requires sub-quadratic attention and therefore only runs for the SSM/hybrid
architectures (rwkv6-7b, zamba2-2.7b) — the skip for the eight pure
full-attention archs is recorded in DESIGN.md §Arch-applicability.
"""

from __future__ import annotations

import dataclasses

from repro.models.common import ArchConfig


@dataclasses.dataclass(frozen=True)
class ShapeCase:
    name: str
    kind: str            # 'train' | 'prefill' | 'decode'
    seq_len: int
    global_batch: int


SHAPES: dict[str, ShapeCase] = {
    "train_4k": ShapeCase("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeCase("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeCase("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeCase("long_500k", "decode", 524_288, 1),
}


def applicable(cfg: ArchConfig, shape: str) -> bool:
    """Is this (arch, shape) cell runnable? (long_500k: sub-quadratic only;
    latent attention is trained only: no serving cache)"""
    if SHAPES[shape].kind != "train" and cfg.mla:
        return False
    if shape == "long_500k":
        return cfg.family in ("ssm", "hybrid")
    return True


def skip_reason(cfg: ArchConfig, shape: str) -> str | None:
    if applicable(cfg, shape):
        return None
    if cfg.mla:
        return f"{cfg.name}: latent attention has no serving cache"
    return (f"{cfg.name} is pure full-attention; a 512k-token dense-attention "
            "decode is skipped per assignment rules (sub-quadratic archs only)")
