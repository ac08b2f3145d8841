"""Tensor-parallel equivalence: tp=2 (vmap'd 'model' axis) == tp=1.

The strongest correctness test in the suite: it validates every manual
collective (embed psum, row-parallel psum, vocab-sharded CE, sharded
argmax), the sharded/replicated flat-storage split (flatten.py), the
gather closures, GQA KV slicing (incl. the replicated-KV path, kv < tp),
expert parallelism, and — via the train test — the full gradient path
through the gathers' transposes.

Archs chosen so tp=2 padding equals tp=1 padding (same math).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SMOKES
from repro.core.gs_sgd import (MeshAxes, _gather_closures, make_state,
                               make_train_step)
from repro.models.common import Spec, init_params, param_specs
from repro.models.flatten import SEG_NAMES, init_flat_params, make_flat_spec
from repro.models.model import decode_fn, init_cache, loss_fn, prefill_fn
from repro.optim import make as make_opt

TP = 2
# granite excluded: 5 experts pad 5->6 at tp=2 (a wider router)
ARCHS_TP = ["qwen3-4b", "starcoder2-3b", "yi-9b", "minicpm-2b",
            "musicgen-large", "rwkv6-7b", "zamba2-2.7b",
            "qwen3-moe-235b-a22b", "llama-3.2-vision-11b"]


def shard_segs(cfg, key, tp):
    """Per-rank local flat segments (stacked on axis 0) + the FlatSpec."""
    params = init_params(cfg, key, tp)      # global (padded) arrays
    specs = param_specs(cfg, tp)
    fs = make_flat_spec(cfg, tp)

    def rank_tree(r):
        def f(arr, spec):
            for axis, ax in enumerate(tuple(spec.pspec)):
                if ax == "model":
                    sz = arr.shape[axis] // tp
                    return jax.lax.slice_in_dim(arr, r * sz, (r + 1) * sz,
                                                axis=axis)
            return arr
        return jax.tree_util.tree_map(
            f, params, specs, is_leaf=lambda x: isinstance(x, Spec))

    segs_r = [fs.flatten(rank_tree(r)) for r in range(tp)]
    stacked = {}
    for k in SEG_NAMES:
        if k.endswith("_r"):  # replicated leaves: store 1/tp slice per rank
            f = segs_r[0][k].shape[-1]
            per = f // tp
            stacked[k] = jnp.stack(
                [segs_r[r][k][..., r * per:(r + 1) * per]
                 for r in range(tp)])
        else:
            stacked[k] = jnp.stack([segs_r[r][k] for r in range(tp)])
    return fs, stacked


def _batch(cfg, B=2, S=12, seed=1):
    k = jax.random.PRNGKey(seed)
    toks = jax.random.randint(k, (B, S), 0, cfg.vocab_size)
    b = {"tokens": toks, "labels": toks}
    if cfg.family == "vlm":
        b["cross_kv"] = 0.02 * jax.random.normal(
            k, (B, cfg.n_cross_tokens, cfg.d_model), jnp.float32)
    return b


def _tp_machinery(cfg):
    ma = MeshAxes(tp=TP, data=1, tp_axis="model", data_axis=None)
    ctx = ma.ctx(jnp.float32)
    gathers = _gather_closures(ma, "dp", jnp.float32)
    return ma, ctx, gathers


@pytest.mark.parametrize("name", ARCHS_TP)
def test_tp_loss_matches_single_device(name):
    cfg = SMOKES[name]
    key = jax.random.PRNGKey(0)
    fs1 = make_flat_spec(cfg, 1)
    segs1 = fs1.flatten(init_params(cfg, key, 1))
    batch = _batch(cfg)
    ref = loss_fn(cfg, MeshAxes(tp=1, data=1, tp_axis=None,
                                data_axis=None).ctx(jnp.float32),
                  fs1, segs1, batch, remat=False)

    fs2, segs2 = shard_segs(cfg, key, TP)
    ma, ctx, gathers = _tp_machinery(cfg)
    losses = jax.vmap(
        lambda s: loss_fn(cfg, ctx, fs2, s, batch, gathers=gathers,
                          remat=False),
        axis_name="model")(segs2)
    np.testing.assert_allclose(np.asarray(losses), float(ref), rtol=2e-4,
                               atol=2e-4)
    assert float(losses[0]) == float(losses[1])  # replicated loss value


@pytest.mark.parametrize("name", ["qwen3-4b", "starcoder2-3b", "rwkv6-7b",
                                  "zamba2-2.7b"])
def test_tp_decode_matches_single_device(name):
    cfg = SMOKES[name]
    key = jax.random.PRNGKey(0)
    B, S, T = 2, 8, 16
    batch = _batch(cfg, B, S)
    ck = batch.get("cross_kv")

    fs1 = make_flat_spec(cfg, 1)
    segs1 = fs1.flatten(init_params(cfg, key, 1))
    ctx1 = MeshAxes(tp=1, data=1, tp_axis=None, data_axis=None).ctx(
        jnp.float32)
    _, cache1 = prefill_fn(cfg, ctx1, fs1, segs1,
                           dict(batch, tokens=batch["tokens"][:, :S - 1]),
                           init_cache(cfg, ctx1, B, T, jnp.float32))
    want, _ = decode_fn(cfg, ctx1, fs1, segs1, batch["tokens"][:, S - 1:],
                        jnp.int32(S - 1), cache1, cross_kv=ck)

    fs2, segs2 = shard_segs(cfg, key, TP)
    ma, ctx2, gathers = _tp_machinery(cfg)
    cache2 = jax.vmap(lambda _: init_cache(cfg, ctx2, B, T, jnp.float32))(
        jnp.arange(TP))

    def pre(s, c):
        return prefill_fn(cfg, ctx2, fs2, s,
                          dict(batch, tokens=batch["tokens"][:, :S - 1]),
                          c, gathers=gathers)

    _, cache2 = jax.vmap(pre, axis_name="model")(segs2, cache2)

    def dec(s, c):
        return decode_fn(cfg, ctx2, fs2, s, batch["tokens"][:, S - 1:],
                         jnp.int32(S - 1), c, cross_kv=ck, gathers=gathers)

    got, _ = jax.vmap(dec, axis_name="model")(segs2, cache2)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(got[1]))


@pytest.mark.parametrize("name", ["qwen3-4b", "starcoder2-3b", "zamba2-2.7b"])
def test_tp_train_matches_single_device(name):
    """3 dense train steps: identical loss trajectory tp=2 vs tp=1 — the
    full gradient path through gather transposes and owned-coord storage."""
    cfg = SMOKES[name]
    key = jax.random.PRNGKey(0)
    opt = make_opt("sgdm", lr=5e-2, momentum=0.9)
    batch = _batch(cfg, B=2, S=12)

    ma1 = MeshAxes(tp=1, data=1, tp_axis=None, data_axis=None)
    ts1 = make_train_step(cfg, ma1, opt, dp_mode="dp", compressor_name=None,
                          remat=False, dtype=jnp.float32)
    st1 = make_state(init_flat_params(cfg, key, 1, ts1.fs), opt, None,
                     ts1.d_local)
    step1 = jax.jit(ts1.fn)

    ma2 = MeshAxes(tp=TP, data=1, tp_axis="model", data_axis=None)
    fs2, segs2 = shard_segs(cfg, key, TP)
    ts2 = make_train_step(cfg, ma2, opt, dp_mode="dp", compressor_name=None,
                          remat=False, dtype=jnp.float32, fs=fs2)
    opt2 = {k: jax.vmap(lambda v, kk=k: opt.init(v.shape))(segs2[k])
            for k in SEG_NAMES}
    st2 = {"params": segs2, "opt": opt2,
           "ef": jnp.zeros((TP, 0), jnp.float32),
           "step": jnp.zeros((TP,), jnp.int32)}
    step2 = jax.jit(jax.vmap(ts2.fn, in_axes=(0, None), axis_name="model"))

    for i in range(3):
        st1, m1 = step1(st1, batch)
        st2, m2 = step2(st2, batch)
        l1, l2 = float(m1["loss"]), float(m2["loss"][0])
        assert abs(l1 - l2) < 5e-4 * max(1.0, abs(l1)), (i, l1, l2)
