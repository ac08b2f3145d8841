"""The benchmark's yardstick tables, pinned to hand arithmetic."""

import json
import os

import pytest

from bench import work
from bench.reference import decoder as dec
from bench.reference.train import sketch_k

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def config(name):
    if name == "starcoder2-3b-l1":   # a wider GQA decoder, no cell of its own
        return dict(config("musicgen-large-l1"), hidden_size=3072,
                    num_attention_heads=24, num_key_value_heads=2,
                    intermediate_size=12288, vocab_size=49152)
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


# d: the flat dimension gs-SGD sketches (segments padded to 512).
# musicgen-large, 1 layer: embed + head 2 x 2048 x 2048 = 8,388,608;
# final norm 2048; q, k, v, o 4 x 2048^2 = 16,777,216 and the MLP
# 3 x 2048 x 8192 = 50,331,648 (67,108,864); two layer norms 4096.
# starcoder2-3b, 1 layer: embed + head 2 x 49152 x 3072 = 301,989,888;
# 3072; q, o 2 x 3072^2 + k, v 2 x 3072 x 256 + MLP 3 x 3072 x 12288
# = 18,874,368 + 1,572,864 + 113,246,208 = 133,693,440; norms 6144.
@pytest.mark.parametrize("name, d", [("musicgen-large-l1", 75_503_616),
                                     ("starcoder2-3b-l1", 435_692_544)])
def test_flat_size(name, d):
    assert dec.flat_size(dec.Decoder.from_config(config(name))) == d


def test_sketch_k_is_four_per_mille():
    s = {"density": 0.004}
    assert sketch_k(s, 75_503_616) == 302_014
    assert sketch_k(s, 1000) == 64


# Forward FLOPs a token, 2 per multiply-add, mean causal context
# (seq + 1) / 2; training is three forwards.
# musicgen, seq 1536: projections 2 x 16,777,216 = 33,554,432;
# MLP 2 x 50,331,648 = 100,663,296; attention 2 x 2 x 2048 x 768.5
# = 6,295,552; head 2 x 2048 x 2048 = 8,388,608; sum 148,901,888.
# starcoder2, seq 4096: projections 2 x (18,874,368 + 1,572,864)
# = 40,894,464; MLP 226,492,416; attention 4 x 3072 x 2048.5
# = 25,171,968; head 2 x 3072 x 49152 = 301,989,888; sum 594,548,736.
@pytest.mark.parametrize("name, seq, forward", [
    ("musicgen-large-l1", 1536, 148_901_888),
    ("starcoder2-3b-l1", 4096, 594_548_736)])
def test_model_flops_per_token(name, seq, forward):
    assert work.model_flops_per_token(config(name), seq) == 3 * forward


def test_encode_work_and_roofline():
    flops, nbytes = work.encode_work(75_503_616, 5, 16384)
    assert flops == 2 * 5 * 75_503_616
    assert nbytes == 4 * 75_503_616 + 4 * 5 * 16384
    with open(os.path.join(ROOT, "bench", "peaks.json")) as f:
        v5e = json.load(f)["TPU v5 lite"]
    # HBM-bound: 302,342,144 B at 819 GB/s
    assert work.roofline_seconds(flops, nbytes, v5e) == pytest.approx(
        302_342_144 / 819e9, rel=1e-12)
    assert work.roofline_seconds(1e15, 1.0, v5e) == pytest.approx(
        1e15 / 197e12)
