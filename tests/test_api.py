"""repro.api: spec round-trips, the one default table vs every generated
CLI, rows coercion, spec-built train steps pinned bit-exact vs the legacy
kwargs, and the three surfaces resolving a shared spec identically."""

import dataclasses
import json

import pytest

from repro import api
from repro.api import (ClusterSpec, ExchangeSpec, RunSpec, SketchSpec,
                       apply_args, build_parser)
from repro.core import compression as comp


# ---------------------------------------------------------------------------
# JSON round-trips
# ---------------------------------------------------------------------------


def test_runspec_json_round_trip():
    spec = RunSpec(
        arch="qwen3-4b", smoke=True, d=123_456, steps=7, seed=3,
        exchange=ExchangeSpec(compressor="gs-sgd", buckets=4, bwd_chunks=2,
                              wire_dtype="bfloat16", allreduce_mode="tree",
                              sketch=SketchSpec(rows="log", width=2048,
                                                k=512, seed=1)),
        cluster=ClusterSpec(p=16, topology="hier", group_size=4,
                            slow_workers={3: 10.0, 7: 2.5},
                            link_alpha=1e-3))
    # through an actual JSON string: dict keys stringify and come back
    back = RunSpec.from_json(json.loads(json.dumps(spec.to_json())))
    assert back == spec
    assert back.cluster.slow_workers == {3: 10.0, 7: 2.5}
    assert back.exchange.sketch.rows == "log"


def test_runspec_file_round_trip_and_schema_guard(tmp_path):
    spec = RunSpec(steps=3, exchange=ExchangeSpec(buckets=2))
    path = str(tmp_path / "spec.json")
    spec.save(path)
    assert RunSpec.load(path) == spec
    (tmp_path / "junk.json").write_text(json.dumps({"schema": "nope"}))
    with pytest.raises(ValueError, match="schema"):
        RunSpec.load(str(tmp_path / "junk.json"))


def test_layers_cut_round_trips_and_keeps_every_width(tmp_path):
    """``RunSpec.layers`` cuts depth only: it survives the spec JSON, the
    generated ``--layers`` flag sets and resets it, and ``arch_config()``
    differs from the published config in ``n_layers`` alone."""
    from repro import obs
    from repro.configs import ARCHS
    spec = RunSpec(arch="musicgen-large", layers=3)
    path = str(tmp_path / "spec.json")
    spec.save(path)
    assert RunSpec.load(path) == spec
    assert json.loads(open(path).read())["layers"] == 3
    ap = build_parser("train")
    assert apply_args(RunSpec(arch="musicgen-large"),
                      ap.parse_args(["--layers", "3"]), "train") == spec
    assert apply_args(spec, ap.parse_args(["--layers", "none"]),
                      "train").layers is None
    pub = ARCHS["musicgen-large"]
    assert spec.arch_config().n_layers == 3
    assert dataclasses.replace(spec.arch_config(),
                               n_layers=pub.n_layers) == pub
    assert RunSpec(arch="musicgen-large").arch_config() == pub
    assert (obs.provenance(spec)["runspec_sha256"]
            != obs.provenance(RunSpec(arch="musicgen-large"))
            ["runspec_sha256"])
    with pytest.raises(ValueError, match="layers"):
        RunSpec(layers=0).validate()


# ---------------------------------------------------------------------------
# the one default table: spec defaults == library defaults == CLI defaults
# ---------------------------------------------------------------------------


def test_sketch_default_table_matches_compression_make():
    """The width-default drift (train 4096 vs comp.make 16384 vs simulate
    None) is fixed by ONE table: SketchSpec. Pin it against the library."""
    gs = comp.make("gs-sgd")
    assert SketchSpec().width == gs.sketch.width == 16384
    assert SketchSpec().rows == gs.sketch.rows == 5
    assert SketchSpec().seed == gs.sketch.seed == 0


@pytest.mark.parametrize("surface", ["train", "sim", "tune", "serve"])
def test_generated_cli_defaults_equal_spec_defaults(surface):
    """Parsing an empty command line on ANY surface resolves to exactly
    the spec defaults — a generated flag whose default drifted from the
    spec would fail here."""
    args = build_parser(surface).parse_args([])
    assert apply_args(RunSpec(), args, surface) == RunSpec()


def test_every_cli_field_help_shows_the_spec_default():
    for path, f, m in api.iter_cli_fields():
        assert m["help"], (path, f.name)
        assert m["flags"][0].startswith("--"), (path, f.name)


def test_explicit_flags_override_spec_base():
    base = RunSpec(exchange=ExchangeSpec(buckets=8),
                   cluster=ClusterSpec(p=32))
    args = build_parser("sim").parse_args(
        ["--p", "16", "--width", "none", "--no-overlap"])
    got = apply_args(base, args, "sim")
    assert got.cluster.p == 16                     # explicit flag wins
    assert got.exchange.buckets == 8               # base inherited
    assert got.exchange.sketch.width is None       # explicit 'none' resets
    assert got.exchange.overlap is False


def test_bool_toggles_override_base_in_both_directions():
    """Every boolean gets an auto-generated inverse flag, so a base spec
    (--spec file or tune plan) can be overridden either way."""
    ap = build_parser("train")
    smoky = RunSpec(smoke=True, remat=False,
                    exchange=ExchangeSpec(overlap=False))
    got = apply_args(smoky, ap.parse_args(
        ["--no-smoke", "--remat", "--overlap"]), "train")
    assert got.smoke is False and got.remat is True
    assert got.exchange.overlap is True
    # inherit when absent; one-way direction still works
    keep = apply_args(smoky, ap.parse_args([]), "train")
    assert keep.smoke is True and keep.exchange.overlap is False
    again = apply_args(RunSpec(), ap.parse_args(["--smoke"]), "train")
    assert again.smoke is True
    # optional strings reset with 'none' instead of creating 'none' paths
    ck = apply_args(RunSpec(ckpt_dir="/tmp/x"),
                    ap.parse_args(["--ckpt-dir", "none"]), "train")
    assert ck.ckpt_dir is None


# ---------------------------------------------------------------------------
# rows normalization: CLI strings coerce in the spec, surfaces see ints
# ---------------------------------------------------------------------------


def test_rows_string_coerces_to_typed_int():
    assert SketchSpec(rows="5") == SketchSpec(rows=5)
    assert SketchSpec(rows="5").rows == 5 and isinstance(
        SketchSpec(rows="5").rows, int)
    with pytest.raises(ValueError, match="rows"):
        SketchSpec(rows="loggg")
    with pytest.raises(ValueError, match="rows"):
        SketchSpec(rows=0)
    # the CLI-string path enforces positivity too, not just the int path
    with pytest.raises(ValueError, match="rows"):
        SketchSpec(rows="0")
    with pytest.raises(ValueError, match="rows"):
        SketchSpec(rows="-3")


def test_sim_config_only_ever_sees_typed_ints():
    """The '5'-vs-5 path: a CLI rows string (and even 'log') reaches
    SimConfig as a plain int — sim/cluster and tune/space never parse."""
    args = build_parser("sim").parse_args(["--rows", "5", "--d", "100000"])
    cfg = apply_args(RunSpec(), args, "sim").sim_config()
    assert cfg.rows == 5 and type(cfg.rows) is int
    assert type(cfg.k) is int and type(cfg.width) is int
    log_cfg = dataclasses.replace(
        RunSpec(d=100_000),
        exchange=ExchangeSpec(sketch=SketchSpec(rows="log"))).sim_config()
    from repro.sim.replay import default_geometry
    assert log_cfg.rows == default_geometry(100_000)[1]
    assert type(log_cfg.rows) is int


def test_slow_workers_flag_parses_and_validates():
    assert api.parse_slow_workers("3:10,7:2.5") == {3: 10.0, 7: 2.5}
    with pytest.raises(ValueError, match="ID:FACTOR"):
        api.parse_slow_workers("3=10")
    with pytest.raises(ValueError, match="> 0"):
        ClusterSpec(slow_workers={3: 0.0}).validate()
    # a hand-authored "slow_workers": null means the same as {}
    assert ClusterSpec(slow_workers=None).slow_workers == {}
    spec = RunSpec.from_json({**RunSpec().to_json(),
                              "cluster": {"slow_workers": None}})
    assert spec.cluster.slow_workers == {}


def test_sim_config_rejects_train_only_compressors():
    """The generated CLI offers every registered compressor, but the
    simulator can only replay four — the spec layer must refuse the rest
    with a clear message, not a KeyError deep in the replay."""
    for name in ("topk", "fetchsgd", "signsgd", "powersgd"):
        bad = dataclasses.replace(RunSpec(d=100_000),
                                  exchange=ExchangeSpec(compressor=name))
        with pytest.raises(ValueError, match="not replayable"):
            bad.sim_config()
    # 'none' maps to the dense baseline instead
    ok = dataclasses.replace(RunSpec(d=100_000),
                             exchange=ExchangeSpec(compressor="none"))
    assert ok.sim_config().method == "dense"


# ---------------------------------------------------------------------------
# central validation: identical messages on every surface
# ---------------------------------------------------------------------------


def test_validation_message_identical_across_surfaces():
    from repro.core.gs_sgd import validate_exchange_config

    bad = ExchangeSpec(bwd_chunks=2, microbatch=2)
    with pytest.raises(ValueError, match="microbatch") as spec_err:
        bad.validate()
    with pytest.raises(ValueError, match="microbatch") as core_err:
        validate_exchange_config(microbatch=2, bwd_chunks=2)
    assert str(spec_err.value) == str(core_err.value)
    # and the tuner's skip reason is the same string
    from repro.tune import Env, SearchSpace, enumerate_valid
    env = Env(p=4, d=100_000, microbatch=2)
    _, skipped = enumerate_valid(
        SearchSpace(buckets=(1,), bwd_chunks=(2,), rows=(3,)), env)
    assert skipped and skipped[0]["reason"] == str(spec_err.value)


def test_spec_validate_rejects_unknown_knobs():
    with pytest.raises(ValueError, match="compressor"):
        ExchangeSpec(compressor="zstd").validate()
    with pytest.raises(ValueError, match="shape"):
        ExchangeSpec(shape="star").validate()
    # wire_dtype only travels end to end on gs-sgd; pricing it for other
    # methods would credit the sim with savings train cannot realize
    with pytest.raises(ValueError, match="wire_dtype"):
        ExchangeSpec(compressor="sketched-sgd",
                     wire_dtype="bfloat16").validate()
    ExchangeSpec(compressor="gs-sgd", wire_dtype="bfloat16").validate()
    with pytest.raises(ValueError, match="topology"):
        ClusterSpec(topology="mesh").validate()
    with pytest.raises(ValueError, match="link"):
        ClusterSpec(link="56k").validate()
    with pytest.raises(ValueError, match="steps"):
        RunSpec(steps=0).validate()


# ---------------------------------------------------------------------------
# spec-built train step == legacy-kwargs train step (bit-exact)
# ---------------------------------------------------------------------------


def test_spec_train_step_bit_exact_vs_legacy_kwargs():
    """``make_train_step(spec=...)`` must be a pure re-expression of the
    legacy kwargs: same compressor object, same schedule, and a run of
    real steps produces a bit-identical loss history."""
    import jax
    import jax.numpy as jnp
    from repro.configs import SMOKES
    from repro.core.gs_sgd import make_state, make_train_step
    from repro.models.flatten import init_flat_params
    from repro.optim import make as make_opt

    cfg = SMOKES["qwen3-4b"]
    spec = RunSpec(
        smoke=True, cluster=ClusterSpec(p=2),
        exchange=ExchangeSpec(buckets=2, sketch=SketchSpec(k=256, rows=3,
                                                           width=512)))
    ma = spec.mesh_axes()
    opt = make_opt("adamw", lr=1e-3)
    legacy = make_train_step(cfg, ma, opt, dp_mode="dp",
                             compressor_name="gs-sgd",
                             compressor_kw=dict(k=256, rows=3, width=512),
                             remat=True, dtype=jnp.float32, buckets=2)
    via_spec = make_train_step(cfg, ma, opt, dp_mode="dp",
                               spec=spec.exchange, remat=True,
                               dtype=jnp.float32)
    assert via_spec.compressor == legacy.compressor
    assert via_spec.n_buckets == legacy.n_buckets == 2

    def run(ts):
        P = 2
        params = init_flat_params(cfg, jax.random.PRNGKey(0), 1, ts.fs)
        state = make_state(params, opt, ts.compressor, ts.d_local)
        state = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (P,) + a.shape), state)
        step = jax.jit(jax.vmap(ts.fn, axis_name="data"))
        losses = []
        for i in range(2):
            toks = jax.random.randint(jax.random.PRNGKey(i), (P, 2, 16), 0,
                                      cfg.vocab_size)
            state, m = step(state, {"tokens": toks, "labels": toks})
            losses.append(float(m["loss"][0]))
        return losses

    assert run(via_spec) == run(legacy)  # bit-exact

    with pytest.raises(ValueError, match="not both"):
        make_train_step(cfg, ma, opt, spec=spec.exchange, buckets=2)


# ---------------------------------------------------------------------------
# one spec file drives train / simulate / tune identically
# ---------------------------------------------------------------------------


def test_three_surfaces_resolve_shared_spec_identically(tmp_path):
    """The CI spec-smoke contract, in-process: loading the same RunSpec
    file as the base on each surface resolves the SAME exchange config."""
    shared = RunSpec(
        smoke=True, steps=2, batch=4, seq=16,
        exchange=ExchangeSpec(buckets=2,
                              sketch=SketchSpec(k=256, rows=3, width=512)),
        cluster=ClusterSpec(p=2))
    path = str(tmp_path / "shared.json")
    shared.save(path)
    resolved = [
        apply_args(RunSpec.load(path), build_parser(s).parse_args([]), s)
        for s in ("train", "sim", "tune")]
    assert resolved[0].exchange == resolved[1].exchange \
        == resolved[2].exchange == shared.exchange
    assert {r.cluster.p for r in resolved} == {2}


def test_example_spec_file_loads_and_validates():
    spec = RunSpec.load("examples/specs/qwen3_smoke.json")
    spec.validate()
    assert spec.smoke and spec.cluster.p >= 2
    # the shared smoke spec must stay sim-resolvable AND trainable
    assert spec.exchange.shape is None
    assert spec.sim_config().d == spec.resolve_d()


def test_wire_dtype_reaches_both_surfaces():
    """The beyond-paper wire knob: bf16 halves sketch bytes in the sim
    replay and sets the compressor's wire dtype in the train step."""
    import jax.numpy as jnp

    f32 = RunSpec(d=100_000).sim_config()
    bf16 = dataclasses.replace(
        RunSpec(d=100_000),
        exchange=ExchangeSpec(wire_dtype="bfloat16")).sim_config()
    assert f32.wire_dtype_bytes == 4 and bf16.wire_dtype_bytes == 2
    from repro.sim import ExchangeReplay, make_network
    net = make_network("flat")
    ids = list(range(4))
    kw = dict(k=512, rows=3, width=1024)
    st32 = ExchangeReplay("gs-sgd", 100_000, **kw).stage_times(net, ids)
    st16 = ExchangeReplay("gs-sgd", 100_000, wire_dtype_bytes=2,
                          **kw).stage_times(net, ids)
    assert sum(st16.t_comm) < sum(st32.t_comm)
    assert st16.bytes_critical < st32.bytes_critical
    kw_train = ExchangeSpec(wire_dtype="bfloat16").compressor_kw(100_000)
    assert kw_train["wire_dtype"] == jnp.bfloat16
    assert ExchangeSpec().compressor_kw(100_000)["wire_dtype"] == jnp.float32
