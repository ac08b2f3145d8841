"""The kanana cell (latent attention, sigmoid-routed held experts, a
leading dense layer; one worker, no exchange) in the harness, on the CPU:
it resolves from ``BENCHMARK.json``, a whole run of its path at the smoke
size comes out correct against ``bench/reference/mla_moe.py``, and with
the timed path broken underneath it does not."""

import json
import os
import shutil

import jax
import jax.numpy as jnp
import pytest

from bench import cell as cells
from bench import program as prg
from bench import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "kanana-mla-moe-b4s8192"
LIMITS = {"loss_gap": 1e-5, "grad_gap": 1e-4, "change_gap": 1e-4,
          "feed_faults": 0}


def test_the_cell_resolves_to_its_files():
    bench = cells.load_benchmark(ROOT)
    c = cells.resolve(bench, CELL, ROOT)
    assert c.chips == 1 and c.traffic["workers"] == 1
    model = cells.reference_module(c.config).Decoder.from_config(c.config)
    assert (model.n_experts, model.experts_held) == (128, 8)
    names = {m["name"] for m in c.per_layer}
    assert {"mla_s_per_step", "moe_route_s_per_step",
            "moe_dispatch_s_per_step", "moe_experts_s_per_step"} <= names
    # the gs-SGD exchange's metrics read nothing in a cell without one
    assert "encode_s_per_step" not in names


def test_the_flat_size_is_the_stated_cut():
    from repro.api import RunSpec
    from repro.models.flatten import make_flat_spec
    c = cells.resolve(cells.load_benchmark(ROOT), CELL, ROOT).config
    model = cells.reference_module(c).Decoder.from_config(c)
    cfg = RunSpec(arch=c["arch"], layers=c["num_hidden_layers"]).arch_config()
    # 424,960,512 parameters; the vocabulary rows padded 16032 -> 16128
    assert cfg.params_count(1) == 424_960_512 + 2 * 96 * 2048
    assert cells.reference_module(c).flat_size(model) == make_flat_spec(
        cfg, 1).total


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The kanana cell at the smoke size, with limits of its own."""
    r = tmp_path_factory.mktemp("kanana_root")
    for d in ("configs", "traffic", "limits"):
        (r / "bench" / d).mkdir(parents=True)
    shutil.copytree(os.path.join(ROOT, "bench", "metrics"),
                    r / "bench" / "metrics")
    with open(os.path.join(ROOT, "bench", "configs",
                           "kanana-2-30b-a3b-l5-ep16.json")) as f:
        c = json.load(f)
    c.update(arch="kanana-2-30b-a3b", smoke=True, num_hidden_layers=3,
             hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
             intermediate_size=96, vocab_size=256, kv_lora_rank=16,
             qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
             n_routed_experts=4, num_experts_per_tok=3,
             moe_intermediate_size=16,
             published=dict(c["published"], n_routed_experts=16))
    (r / "bench" / "configs" / "tiny.json").write_text(json.dumps(c))
    with open(os.path.join(ROOT, "bench", "traffic",
                           "single-b4s8192.json")) as f:
        t = json.load(f)
    t.update(global_batch=4, seq=16)
    (r / "bench" / "traffic" / "tiny.json").write_text(json.dumps(t))
    (r / "bench" / "limits" / "tiny-kanana.json").write_text(
        json.dumps(LIMITS))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"] = [{"name": "tiny", "source": "test",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test"}]
    b["workloads"] = [{"name": "tiny-kanana", "config": "tiny",
                       "traffic": "tiny", "chips": 1, "why": "test"}]
    b["per_layer"] = [m for m in b["per_layer"] if "workloads" not in m]
    (r / "BENCHMARK.json").write_text(json.dumps(b))
    return str(r)


def result(root, capsys, program_class, seed):
    rc = run.main(["--workload", "tiny-kanana", "--seed", str(seed),
                   "--seconds", "0.2", "--trace", "0"], root=root,
                  require_tpu=False, hooks={"Program": program_class})
    out = capsys.readouterr()
    assert rc == 0
    return json.loads(out.out.strip().splitlines()[-1])


class Unchanged(prg.Program):
    """The step returns its state unchanged."""

    def dispatch(self, batch):
        copy = jax.tree_util.tree_map(jnp.copy, self.state)
        return self.compiled(copy, batch)[1]


class HalfBatch(prg.Program):
    """Half of the rows left out, the mean taken over the rest."""

    @staticmethod
    def _half(batch):
        return jax.tree_util.tree_map(lambda a: a[:a.shape[0] // 2], batch)

    def compile_step(self, ts, state, batch):
        return super().compile_step(ts, state, self._half(batch))

    def dispatch(self, batch):
        return super().dispatch(self._half(batch))


class AlteredLoss(prg.Program):
    """The reported loss altered where it is produced, by 1%."""

    @staticmethod
    def loss(m):
        return prg.Program.loss(m) * 1.01


class BiasFrozen(prg.Program):
    """The selection bias never moves."""

    def dispatch(self, batch):
        bias = self.state["router_bias"]
        m = super().dispatch(batch)
        self.state["router_bias"] = jnp.zeros_like(bias)
        return m


def test_sound_run_is_correct(root, capsys):
    doc = result(root, capsys, prg.Program, 2**31 + 21)
    assert doc["correct"] is True, doc["check"]


@pytest.mark.parametrize("fault", [Unchanged, HalfBatch, AlteredLoss,
                                   BiasFrozen], ids=lambda c: c.__name__)
def test_broken_timed_path_is_not_correct(root, capsys, fault):
    assert result(root, capsys, fault, 2**31 + 22)["correct"] is False
