"""The reference's first training steps: the numbers ``correct`` compares.

``follow`` runs the configuration's reference model (``decoder.py``) and
the traffic's exchange and optimizer (``exchange.py``) from the seed over
the batches the program was fed, and returns per step the reported loss
(mean over workers), after step 1 the norm of every leaf of the gradient
the optimizer applied, and after the last step the norm of every leaf's
change from the initial weights.

``fault`` plants one of the faults the comparison has to catch in this
reference, for reading how far each moves the compared numbers:

- ``half_batch``: every worker drops the second half of its rows and
  takes the mean over the rest;
- ``no_exchange``: no worker adds the others' contribution: worker 0
  applies its own gradient (gs-SGD: its own sketch's selection), not
  divided by the worker count, and its view is returned;
- ``bf16``: weights, activations and gradients in bfloat16 at default
  matmul precision, the weights kept in bfloat16 between steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference import exchange as ex

FAULTS = ("half_batch", "no_exchange", "bf16")


@jax.jit
def _norms(tree: dict) -> dict:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for k, v in tree.items()}


def leaf_norms(tree: dict) -> dict:
    return {k: float(v) for k, v in _norms(tree).items()}


@functools.lru_cache(maxsize=None)
def _adam_fn(opt: ex.AdamW, step: int):
    """AdamW over every leaf, in f32, results cast back to the leaf's
    dtype."""
    def apply(p, g, m, v):
        out = jax.tree_util.tree_map(
            lambda a, b, c, e: tuple(x.astype(a.dtype) for x in opt.apply(
                a.astype(jnp.float32), b.astype(jnp.float32),
                c.astype(jnp.float32), e.astype(jnp.float32), step)),
            p, g, m, v)
        return tuple({k: out[k][i] for k in p} for i in range(3))
    return jax.jit(apply)


def follow(dec, model, traffic: dict, seed: int, batches: list,
           *, fault: str | None = None) -> dict:
    """dec: the configuration's reference module, model: its sizes.
    batches: per step {"tokens", "labels"} of the GLOBAL batch (B, S), as
    numpy or device arrays; worker p holds rows [p B/P, (p+1) B/P)."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    P = traffic["workers"]
    o = traffic["optimizer"]
    opt = ex.AdamW(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                   weight_decay=o["weight_decay"])
    comp = traffic["compressor"]
    sk = None
    if comp == "gs-sgd":
        s = traffic["sketch"]
        sk = ex.Sketch(rows=s["rows"], width=s["width"],
                       k=sketch_k(s, dec.flat_size(model)), seed=s["seed"])
    elif comp != "none":
        raise ValueError(f"no reference for compressor {comp!r}")
    low = fault == "bf16"
    dtype = jnp.bfloat16 if low else jnp.float32
    precision = jax.lax.Precision.DEFAULT if low else dec.HIGHEST

    params = dec.init_params(model, seed)
    if low:
        params = jax.tree_util.tree_map(lambda a: a.astype(dtype), params)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    efs = None
    losses, grad_norms = [], None
    for step, batch in enumerate(batches):
        tok = jnp.asarray(batch["tokens"])
        lab = jnp.asarray(batch["labels"])
        per = tok.shape[0] // P
        grads, step_loss = [], 0.0
        for p in range(P):
            t_p, l_p = tok[p * per:(p + 1) * per], lab[p * per:(p + 1) * per]
            if fault == "half_batch":
                t_p, l_p = t_p[:max(1, per // 2)], l_p[:max(1, per // 2)]
            l, g = dec.loss_and_grad(model, params, t_p, l_p, dtype=dtype,
                                     precision=precision)
            step_loss += float(l) / P
            grads.append(g)
        losses.append(step_loss)
        if fault == "no_exchange":
            grads = grads[:1]
        n = len(grads)
        if comp == "none":
            g_mean = jax.tree_util.tree_map(
                lambda *gs: sum(gs[1:], gs[0]) / n, *grads)
        else:
            us = [dec.pack(model, jax.tree_util.tree_map(
                lambda a: a.astype(jnp.float32), g)) for g in grads]
            if efs is not None:
                us = [e + u for e, u in zip(efs, us)]
            applied, efs = ex.gs_sgd(sk, us)
            g_mean = dec.unpack(model, applied / n)
        del grads
        if step == 0:
            grad_norms = leaf_norms(g_mean)
        params, m, v = _adam_fn(opt, step)(params, g_mean, m, v)
    p0 = dec.init_params(model, seed)
    change = leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a.astype(jnp.float32) - b, params, p0))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def sketch_k(s: dict, d: int) -> int:
    """Selected coordinates a step: the traffic's density of the flat
    dimension, at least 64."""
    return max(64, int(s["density"] * d))
