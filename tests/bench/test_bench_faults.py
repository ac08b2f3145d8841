"""Whole harness runs of the tiny cell (``conftest.py``) with the timed
path broken underneath: ``correct`` comes out false. Sound, it comes out
true."""

import json
import unittest.mock

import jax
import jax.numpy as jnp
import pytest

from bench import program as prg


class Unchanged(prg.Program):
    """The step returns its state unchanged."""

    def dispatch(self, batch):
        copy = jax.tree_util.tree_map(jnp.copy, self.state)
        return self.compiled(copy, batch)[1]


class HalfBatch(prg.Program):
    """Half of each worker's rows left out, the mean taken over the
    rest."""

    @staticmethod
    def _half(batch):
        return jax.tree_util.tree_map(
            lambda a: a[:, :a.shape[1] // 2], batch)

    def compile_step(self, ts, state, batch):
        return super().compile_step(ts, state, self._half(batch))

    def dispatch(self, batch):
        return super().dispatch(self._half(batch))


class AlteredLoss(prg.Program):
    """The reported loss altered where it is produced, by 1%."""

    @staticmethod
    def loss(m):
        return prg.Program.loss(m) * 1.01


class NoExchange(prg.Program):
    """Every cross-worker sum left out: each worker keeps its own."""

    def compile_step(self, ts, state, batch):
        with unittest.mock.patch.object(jax.lax, "psum",
                                        lambda x, axis_name, **kw: x):
            return super().compile_step(ts, state, batch)


class OneWorkerStale(prg.Program):
    """Worker 1's parameters kept as they were: only worker 0 steps."""

    def dispatch(self, batch):
        keep = jax.tree_util.tree_map(lambda a: a[1],
                                      self.state["params"])
        m = super().dispatch(batch)
        self.state["params"] = jax.tree_util.tree_map(
            lambda a, k: a.at[1].set(k), self.state["params"], keep)
        return m


class RepeatedFeed(prg.Program):
    """Every step fed the first step's batch."""

    def batch(self, step):
        return super().batch(0)


def test_sound_run_is_correct(harness):
    doc = harness(prg.Program, 2**31 + 11)
    assert doc["correct"] is True
    assert all(v["value"] <= v["limit"] for v in doc["check"].values())


@pytest.mark.parametrize("fault", [Unchanged, HalfBatch, AlteredLoss,
                                   NoExchange, OneWorkerStale,
                                   RepeatedFeed],
                         ids=lambda c: c.__name__)
def test_broken_timed_path_is_not_correct(harness, fault):
    assert harness(fault, 2**31 + 12)["correct"] is False


def test_traced_run_reports_per_layer_metrics(root, capsys, monkeypatch):
    # the CPU has no device trace: the reduction of a recorded v5e step
    # stands in for it, so that every reader runs on the harness's record
    import os

    from bench import run, trace_reduce as tr
    data = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "v5e-musicgen-gs-sgd-p2-step.json.gz")
    monkeypatch.setattr(tr, "reduce_dir", lambda d, n: tr.reduce_events(
        *tr.load_events(data)))
    with open(os.path.join(root, "bench", "peaks.json"), "w") as f:
        f.write('{"cpu": {"bf16_flops_per_s": 1e12, '
                '"hbm_bytes_per_s": 1e11}}')
    rc = run.main(["--workload", "tiny-gs", "--seed", "5", "--seconds",
                   "0.2", "--trace", "1"], root=root, require_tpu=False)
    out = capsys.readouterr()
    assert rc == 0
    doc = json.loads(out.out.strip().splitlines()[-1])
    assert doc["correct"] is True
    assert set(doc["metrics"]) >= {"input_s_per_step", "compiles_per_step",
                                   "mfu", "encode_s_per_step",
                                   "encode_roofline",
                                   "recover_sort_s_per_step", "idle_share"}
    assert doc["device"]["busy_s"] <= doc["device"]["window_s"]
    assert len(doc["breakdown"]["device_ops"]) <= 10
    assert list(doc)[-1] == "check"
