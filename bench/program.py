"""The system under test, built and driven through the program's own entry.

The build is ``chip_smoke.py``'s and the loop is ``launch/train.main``'s:
``RunSpec`` -> ``train.build`` -> ``train.init_state`` (weights made on
the device from the seed) -> ``train.make_step_fn(ts, P)`` lowered and
compiled for the state and the first batch; then per step
``train.worker_batch(LMStream)`` -> the compiled step -> the loss fetched
to the host. A change to the program is measured here only where it lies
on that path (``launch/train.main``'s own loop is not run).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class SizeMismatch(ValueError):
    """The configuration file does not describe the model that runs."""


class Program:
    def __init__(self, cell, seed: int, ref, model):
        from repro.api import ClusterSpec, ExchangeSpec, RunSpec, SketchSpec
        from repro.data import LMStream
        from repro.launch import train

        from bench.reference.train import sketch_k

        c, t = cell.config, cell.traffic
        self._train = train
        self.P = t["workers"]
        sk = t.get("sketch")
        exchange = ExchangeSpec(
            compressor=t["compressor"],
            allreduce_mode=t.get("allreduce_mode", "psum"),
            sketch=(SketchSpec(rows=sk["rows"], width=sk["width"],
                               k=sketch_k(sk, ref.flat_size(model)),
                               seed=sk["seed"]) if sk else SketchSpec()))
        self.spec = RunSpec(
            arch=c["arch"], smoke=bool(c.get("smoke", False)),
            layers=c["num_hidden_layers"], batch=t["global_batch"],
            seq=t["seq"], lr=t["optimizer"]["lr"],
            optimizer=t["optimizer"]["name"], seed=int(seed),
            cluster=ClusterSpec(p=self.P), exchange=exchange)
        self.spec.validate()
        cfg, opt, _, ts = train.build(self.spec)
        check_sizes(c, cfg, ts.d_local, ref.flat_size(model))
        self.stream = LMStream(vocab_size=cfg.vocab_size, seq_len=t["seq"],
                               global_batch=t["global_batch"], seed=int(seed))
        state = train.init_state(self.spec, cfg, opt, ts)
        b0 = train.worker_batch(self.stream, 0, self.spec)
        self.compiled = self.compile_step(ts, state, b0)
        in_state, in_batch = self.compiled.input_shardings[0]
        self.state = jax.device_put(state, in_state)
        self._batch_sharding = in_batch

    def compile_step(self, ts, state, batch):
        """``make_step_fn`` lowered for the state and batch, compiled."""
        return self._train.make_step_fn(ts, self.P).lower(
            state, batch).compile()

    def batch(self, step: int) -> dict:
        return jax.device_put(
            self._train.worker_batch(self.stream, step, self.spec),
            self._batch_sharding)

    def dispatch(self, batch: dict) -> dict:
        """Run the compiled step; returns its metrics without waiting."""
        self.state, m = self.compiled(self.state, batch)
        return m

    @staticmethod
    def loss(m: dict) -> float:
        """The step's reported loss, fetched to the host (waits for the
        step)."""
        return float(np.asarray(jax.device_get(m["loss"])).reshape(-1)[0])

    def _workers(self, tree) -> list:
        """Every worker's copy (the leading P axis split off)."""
        return [tree] if self.P == 1 else [
            jax.tree_util.tree_map(lambda a, p=p: a[p], tree)
            for p in range(self.P)]

    def params_host(self) -> list:
        """Every worker's parameter segments, copied to the host."""
        return self._workers(jax.device_get(self.state["params"]))

    def first_moment(self) -> list:
        """Every worker's AdamW first moment, by segment."""
        return self._workers({k: mv[0] for k, mv in
                              self.state["opt"].items()})

    def close(self) -> None:
        """Free the program's device state."""
        for leaf in jax.tree_util.tree_leaves(self.state):
            leaf.delete()
        self.state = None
        self.compiled = None


def check_sizes(c: dict, cfg, d_local: int, d_ref: int) -> None:
    got = {"num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
           "num_attention_heads": cfg.n_heads,
           "num_key_value_heads": cfg.n_kv_heads,
           "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
           "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps}
    bad = {k: (c[k], v) for k, v in got.items() if c[k] != v}
    if bad or d_local != d_ref:
        raise SizeMismatch(f"configuration file vs program (file, program): "
                           f"{bad}, flat size {(d_ref, d_local)}")


def adam_grad_norms(ref, model, m_segs, b1: float) -> dict:
    """Norm of every leaf of the first applied gradient, read from AdamW's
    first moment after one step (m = (1 - b1) g)."""
    @jax.jit
    def norms(m):
        leaves = ref.leaves_of_segments(model, m)
        return {k: jnp.sqrt(jnp.sum(jnp.square(v))) / (1.0 - b1)
                for k, v in leaves.items()}
    return {k: float(v) for k, v in norms(m_segs).items()}


def change_norms(ref, model, p0: dict, p3: dict) -> dict:
    """Norm of every leaf's change between two host copies of the params
    segments, in float64."""
    diff = {k: p3[k].astype(np.float64) - p0[k] for k in p0}
    return {k: float(np.linalg.norm(v)) for k, v in
            ref.leaves_of_segments(model, diff).items()}
