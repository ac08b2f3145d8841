"""The train step names its phases on the device (DESIGN.md §10).

Each phase of the compiled step carries its ``repro.obs`` scope in the
HLO ``op_name`` metadata, which the device trace reports per op and
``bench/phases.py`` reads. Pinned here on the compiled CPU step at a tiny
size, for the monolithic gs-SGD exchange, the bucketed and the
backward-interleaved schedules, and the dense baseline.
"""

import collections

import pytest

from bench import phases
from repro import obs
from repro.api import ClusterSpec, ExchangeSpec, RunSpec
from repro.data import LMStream
from repro.launch import train

GS = set(obs.SCOPES) - set(obs.BLOCK_SCOPES)   # musicgen has no such block
DENSE = {"forward", "backward", "comm", "optimizer"}
EXCHANGES = {
    "monolithic": ExchangeSpec(compressor="gs-sgd"),
    "bucketed": ExchangeSpec(compressor="gs-sgd", buckets=2),
    "interleaved": ExchangeSpec(compressor="gs-sgd", buckets=2,
                                bwd_chunks=2, fuse_encode=True),
    "dense": ExchangeSpec(compressor="none"),
}
_OPS: dict = {}


def step_ops(name: str, arch: str = "musicgen-large",
             layers: int | None = 1) -> collections.Counter:
    """{(phase, the op's primitive): number of HLO instructions} of the
    compiled two-worker step, compiled once a process."""
    if (name, arch) not in _OPS:
        spec = RunSpec(arch=arch, smoke=True, layers=layers, batch=4,
                       seq=16, cluster=ClusterSpec(p=2),
                       exchange=EXCHANGES[name])
        cfg, opt, _, ts = train.build(spec)
        state = train.init_state(spec, cfg, opt, ts)
        stream = LMStream(vocab_size=cfg.vocab_size, seq_len=16,
                          global_batch=4)
        batch = train.worker_batch(stream, 0, spec)
        text = train.make_step_fn(ts, 2).lower(state,
                                               batch).compile().as_text()
        _OPS[name, arch] = collections.Counter(
            (phases.phase_of(path), path.rsplit("/", 1)[-1])
            for path in phases.scope_paths(text).values())
    return _OPS[name, arch]


@pytest.mark.parametrize("exchange, want", [
    ("monolithic", GS), ("bucketed", GS), ("interleaved", GS),
    ("dense", DENSE),
])
def test_every_phase_is_named_in_the_compiled_step(exchange, want):
    got = collections.Counter()
    for (ph, _), n in step_ops(exchange).items():
        got[ph] += n
    missing = want - {p for p in got if p is not None}
    assert not missing, f"no op of the step carries {sorted(missing)}"
    # the scopes cover most of the step's instructions
    assert got.get(None, 0) < 0.4 * sum(got.values())
    if want is DENSE:
        assert not {p for p in got if p and p.startswith("recover")}


@pytest.mark.parametrize("exchange", ["monolithic", "bucketed",
                                      "interleaved"])
def test_second_round_holds_its_gather_and_both_scatters(exchange):
    # the gather of Top_k's values and the scatter of the exact sum
    # (``_recover``) and the EF residual's scatter (``stage_recover``)
    # lie under recover/second_round; with either scope gone they fall
    # to the enclosing ``recover`` stage
    ops = step_ops(exchange)
    assert ops[("recover/second_round", "gather")] >= 1
    assert ops[("recover/second_round", "scatter")] >= 2
    stray = {op: n for (ph, op), n in ops.items()
             if ph == "recover" and op in ("gather", "scatter")}
    assert not stray, f"second-round ops outside its scope: {stray}"


def test_dense_pack_is_not_comm():
    # the dense baseline's gradient pack is under encode, as in gs-SGD;
    # comm holds the psum alone
    ops = step_ops("dense")
    assert not ops[("comm", "concatenate")]


def test_block_scopes_are_named_in_a_latent_attention_moe_step():
    # kanana's blocks: latent attention, routing, dispatch and the experts'
    # grouped matmuls each carry their scope; the gs-SGD phases still do
    ops = step_ops("monolithic", "kanana-2-30b-a3b", None)
    got = {ph for (ph, _), n in ops.items() if n}
    assert set(obs.SCOPES) <= got
    assert ops[("moe_dispatch", "sort")] >= 1
    assert ops[("moe_route", "sort")] >= 1 or ops[("moe_route", "top_k")] >= 1
