"""The work a step must do, computed from the configuration's shapes.

These are what a roofline share or a FLOP utilization divides by, so they
count what any implementation must do, not what this one happens to do:
recomputation under remat and padding are left out.
"""

from __future__ import annotations


def model_flops_per_token(config: dict, seq: int) -> float:
    """Forward and backward FLOPs a token of a sequence of ``seq`` costs a
    pre-norm decoder: 2 per multiply-add of every projection, the SwiGLU
    MLP and the output head, plus causal attention's scores and values
    over the mean context (seq + 1) / 2; the backward is twice the
    forward."""
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    g = config["num_key_value_heads"]
    f = config["intermediate_size"]
    hd = d // h
    proj = d * h * hd * 2 + d * g * hd * 2          # q, o and k, v
    mlp = 3 * d * f
    attn = 2 * h * hd * (seq + 1) / 2               # scores and values
    per_layer = 2 * (proj + mlp) + 2 * attn
    forward = config["num_hidden_layers"] * per_layer \
        + 2 * d * config["vocab_size"]
    return 3.0 * forward


def encode_work(d: int, rows: int, width: int) -> tuple[float, float]:
    """(FLOPs, HBM bytes) of one Count-Sketch encode of a length-d f32
    vector into a (rows, width) f32 table: a multiply-add per coordinate
    and row, reading the vector once and writing the table once."""
    return 2.0 * rows * d, 4.0 * d + 4.0 * rows * width


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
