"""The reference's planted faults read what they claim to, at a tiny
size on the CPU."""

import json
import os

import numpy as np
import pytest

from bench.reference import decoder as dec
from bench.reference import train as rtrain

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def read(name):
    with open(os.path.join(ROOT, "bench", name)) as f:
        return json.load(f)


@pytest.mark.parametrize("compressor", ["none", "gs-sgd"])
def test_no_exchange_applies_worker_zeros_own_gradient(compressor):
    # worker 0 alone, unscaled: as if it were the only worker, on its rows
    c = read("configs/musicgen-large-l1.json")
    c.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             intermediate_size=128, vocab_size=64)
    model = dec.Decoder.from_config(c)
    t = dict(read("traffic/gs-sgd-p2-b8s1536.json"), global_batch=4, seq=8,
             compressor=compressor)
    t["sketch"] = dict(t["sketch"], width=8192, density=0.05)
    rng = np.random.default_rng(0)
    seqs = rng.integers(0, 64, size=(3, 4, 9))
    fed = [{"tokens": s[:, :-1], "labels": s[:, 1:]} for s in seqs]
    cut = rtrain.follow(dec, model, t, 7, fed, fault="no_exchange")
    alone = rtrain.follow(dec, model, dict(t, workers=1, global_batch=2), 7,
                          [{k: v[:2] for k, v in b.items()} for b in fed])
    for k in ("grad_norms", "change_norms"):
        assert cut[k].keys() == alone[k].keys()
        np.testing.assert_allclose([cut[k][n] for n in cut[k]],
                                   [alone[k][n] for n in alone[k]],
                                   rtol=1e-6)
