"""Per-architecture smoke tests (reduced same-family configs, CPU).

For each registered arch's smoke config: forward loss (finite, ~log V at init),
one train step (loss decreases over a few steps), prefill/decode
consistency (incremental decoding reproduces the full-forward argmax).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SMOKES
from repro.core.gs_sgd import MeshAxes, make_state, make_train_step
from repro.models.common import ShardCtx
from repro.models.flatten import init_flat_params, make_flat_spec
from repro.models.model import decode_fn, init_cache, loss_fn, prefill_fn
from repro.optim import make as make_opt

CTX = ShardCtx(tp=1, tp_axis=None, dtype=jnp.float32)
ALL = sorted(SMOKES)
# serving has no latent cache (tests/test_mla_moe.py pins the refusal)
SERVED = [n for n in ALL if not SMOKES[n].mla]


def _batch(cfg, B=2, S=16, seed=1):
    k = jax.random.PRNGKey(seed)
    toks = jax.random.randint(k, (B, S), 0, cfg.vocab_size)
    b = {"tokens": toks, "labels": toks}
    if cfg.family == "vlm":
        b["cross_kv"] = 0.02 * jax.random.normal(
            k, (B, cfg.n_cross_tokens, cfg.d_model), jnp.float32)
    return b


@pytest.mark.parametrize("name", ALL)
def test_loss_finite_and_calibrated(name):
    cfg = SMOKES[name]
    fs = make_flat_spec(cfg, 1)
    segs = init_flat_params(cfg, jax.random.PRNGKey(0), 1, fs)
    loss = loss_fn(cfg, CTX, fs, segs, _batch(cfg), remat=False)
    assert jnp.isfinite(loss)
    # init loss ~ log(vocab) (exact for untied; tied embeddings lower it)
    assert 0.3 * np.log(cfg.vocab_size) < float(loss) \
        < 1.3 * np.log(cfg.vocab_size) + 1.0


@pytest.mark.parametrize("name", ALL)
def test_train_step_reduces_loss(name):
    cfg = SMOKES[name]
    ma = MeshAxes(tp=1, data=1, tp_axis=None, data_axis=None)
    opt = make_opt("adamw", lr=2e-3)
    ts = make_train_step(cfg, ma, opt, dp_mode="dp", compressor_name=None,
                         remat=True, dtype=jnp.float32)
    params = init_flat_params(cfg, jax.random.PRNGKey(0), 1, ts.fs)
    state = make_state(params, opt, None, ts.d_local, cfg=cfg)
    step = jax.jit(ts.fn)
    batch = _batch(cfg, B=2, S=16)
    losses = []
    for _ in range(4):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
        assert np.isfinite(losses[-1])
    assert losses[-1] < losses[0]
    for k, v in state["params"].items():
        assert bool(jnp.all(jnp.isfinite(v))), k


@pytest.mark.parametrize("name", SERVED)
def test_prefill_decode_consistency(name):
    cfg = SMOKES[name]
    fs = make_flat_spec(cfg, 1)
    segs = init_flat_params(cfg, jax.random.PRNGKey(0), 1, fs)
    B, S, T = 2, 12, 32
    b = _batch(cfg, B, S)
    ck = b.get("cross_kv")
    lg, _ = prefill_fn(cfg, CTX, fs, segs, b,
                       init_cache(cfg, CTX, B, T, jnp.float32))
    want = jnp.argmax(lg, -1)
    b2 = dict(b, tokens=b["tokens"][:, :S - 1])
    _, cache = prefill_fn(cfg, CTX, fs, segs, b2,
                          init_cache(cfg, CTX, B, T, jnp.float32))
    got, cache = decode_fn(cfg, CTX, fs, segs, b["tokens"][:, S - 1:],
                           jnp.int32(S - 1), cache, cross_kv=ck)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    # a further decode step still runs and the cache advances
    got2, _ = decode_fn(cfg, CTX, fs, segs, got[:, None], jnp.int32(S),
                        cache, cross_kv=ck)
    assert got2.shape == (B,)


@pytest.mark.parametrize("name", ALL)
def test_full_config_parameter_counts(name):
    """The FULL (non-smoke) configs instantiate specs with sane counts —
    pure shape math, no allocation."""
    cfg = ARCHS[name]
    n = cfg.params_count(tp=16)
    expected = {
        "llama-3.2-vision-11b": (9e9, 13e9),
        "qwen3-moe-235b-a22b": (200e9, 260e9),
        "granite-moe-3b-a800m": (2.5e9, 4.5e9),
        "qwen3-4b": (3e9, 5e9),
        "yi-9b": (8e9, 10e9),
        "minicpm-2b": (2e9, 3.6e9),
        # starcoder2's published 3B uses a 2-matrix GELU MLP; our unified
        # SwiGLU block (3 matrices at the same published d_ff=12288) lands
        # at ~4.5B — shapes faithful, layout documented in DESIGN.md.
        "starcoder2-3b": (3.9e9, 4.7e9),
        "rwkv6-7b": (6e9, 9e9),
        "musicgen-large": (2.5e9, 4e9),
        "zamba2-2.7b": (2e9, 3.5e9),
        "kanana-2-30b-a3b": (29e9, 32e9),
    }[name]
    assert expected[0] < n < expected[1], f"{name}: {n / 1e9:.2f}B params"


def test_moe_aux_loss_present():
    cfg = SMOKES["qwen3-moe-235b-a22b"]
    fs = make_flat_spec(cfg, 1)
    segs = init_flat_params(cfg, jax.random.PRNGKey(0), 1, fs)
    from repro.models import moe as moe_lib
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
    cyc = fs.cycle_params(segs["cycles_s"][0], segs["cycles_r"][0],
                          jnp.float32)
    p = jax.tree_util.tree_map(lambda a: a[0], cyc["moe"])  # occurrence 0
    y, aux, _ = moe_lib.moe_block(p["moe"], cfg, CTX, h)
    assert y.shape == h.shape
    assert float(aux) > 0.0  # Switch aux loss >= 1 at balance, > 0 always
