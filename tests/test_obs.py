"""repro.obs — span tracing, trace@2 metrics, provenance, overlap audit.

Pins the PR's acceptance criteria: span nesting well-formedness, the
trace@2 strict-superset round-trip through ``tune.calibrate`` (warmup
tags replacing the positional drop), sim and train exports sharing one
span schema, structured runtime events from failure injection, the
sim-trace overlap-audit self-check, and — most important — ZERO overhead
when tracing is off: a run with ``--trace``/``--json`` produces a loss
history bit-identical to one without (the probe's output is discarded;
the NULL tracer leaves the jitted step untouched).
"""

import dataclasses
import json

import numpy as np
import pytest

from repro import obs
from repro.api import RunSpec
from repro.obs import trace as obtrace
from repro.tune import calibrate

STEPS = 3
TRAIN_ARGV = ["--smoke", "--workers", "2", "--steps", str(STEPS),
              "--batch", "4", "--seq", "16", "--compressor", "gs-sgd",
              "--buckets", "2", "--bwd-chunks", "2", "--log-every", "5"]


# ---------------------------------------------------------------------------
# Tracer core
# ---------------------------------------------------------------------------


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_span_pairing_nesting_and_export():
    clk = FakeClock()
    tr = obs.Tracer(clock=clk, epoch=0.0)
    with tr.span("step0", cat="step"):
        clk.t = 1.0
        with tr.span("encode/b0", cat="encode") as sp:
            assert sp.sync([1, 2]) == [1, 2]   # identity on non-arrays
            clk.t = 2.0
        with tr.span("allreduce/b0", cat="comm"):
            clk.t = 3.0
        clk.t = 4.0
    tr.instant("ready/b1", cat="encode", args={"bucket": 1})
    assert obtrace.validate(tr) == 3
    doc = tr.to_chrome(spec={"p": 2}, provenance={"host": "x"})
    assert doc["schema"] == obs.TRACE_SCHEMA
    assert obtrace.validate(doc) == 3
    # µs conversion + nesting preserved through export
    enc = obtrace.spans(doc, cat="encode")
    assert enc[0]["dur"] == pytest.approx(1.0)
    assert obtrace.instants(doc, "ready/b1")[0]["args"] == {"bucket": 1}
    assert obtrace.phase_totals(doc)["step"] == pytest.approx(4.0)


def test_out_of_order_end_raises():
    tr = obs.Tracer(clock=FakeClock(), epoch=0.0)
    a = tr.begin("a")
    tr.begin("b")
    with pytest.raises(ValueError, match="out of order"):
        tr.end(a)


def test_export_refuses_open_spans():
    tr = obs.Tracer(clock=FakeClock(), epoch=0.0)
    tr.begin("dangling")
    with pytest.raises(ValueError, match="open spans"):
        tr.to_chrome()


def test_validate_rejects_overlapping_spans():
    tr = obs.Tracer(epoch=0.0)
    tr.add_span("a", 0.0, 2.0)
    tr.add_span("b", 1.0, 3.0)   # overlaps a without nesting
    with pytest.raises(ValueError, match="without nesting"):
        obtrace.validate(tr)


def test_null_tracer_is_inert_and_ambient_restores():
    assert obtrace.current() is obtrace.NULL
    sp = obtrace.current().span("x", cat="encode")
    assert sp.sync("y") == "y"
    with sp:
        pass                         # shared no-op span: no state anywhere
    tr = obs.Tracer(clock=FakeClock(), epoch=0.0)
    with tr.activate():
        assert obtrace.current() is tr
        with pytest.raises(RuntimeError):
            with tr.activate():
                raise RuntimeError("boom")
        assert obtrace.current() is tr   # inner exit restored correctly
    assert obtrace.current() is obtrace.NULL


def test_bucket_durations_ordering():
    clk = FakeClock()
    tr = obs.Tracer(clock=clk, epoch=0.0)
    for i, dur in ((1, 0.5), (0, 0.25)):   # out of bucket order on purpose
        sp = tr.begin(f"encode/b{i}", cat="encode")
        clk.t += dur
        tr.end(sp)
    doc = tr.to_chrome()
    assert obtrace.bucket_durations(doc, "encode", "encode/b") == \
        pytest.approx([0.25, 0.5])


def test_save_load_roundtrip(tmp_path):
    clk = FakeClock()
    tr = obs.Tracer(clock=clk, epoch=0.0)
    with tr.span("step0", cat="step"):
        clk.t = 1.0
    p = str(tmp_path / "t.json")
    tr.save(p, spec={"p": 4}, provenance={"schema": "x"}, source="train")
    doc = obtrace.load(p)
    assert doc["source"] == "train" and doc["spec"] == {"p": 4}
    assert obtrace.validate(doc) == 1
    with pytest.raises(ValueError, match="not a"):
        bad = str(tmp_path / "bad.json")
        with open(bad, "w") as f:
            json.dump({"schema": "nope"}, f)
        obtrace.load(bad)


# ---------------------------------------------------------------------------
# Device scopes, the profiler's clock, compiles by function
# ---------------------------------------------------------------------------


def test_phase_names_the_ops_and_opens_the_span():
    import jax
    import jax.numpy as jnp

    def f(x):
        with obtrace.phase("encode", "encode/b0") as sp:
            return sp.sync(jnp.sin(x) * 2)

    text = jax.jit(f).lower(jnp.ones(8)).as_text(debug_info=True)
    assert "/encode/sin" in text and "/encode/mul" in text
    clk = FakeClock()
    tr = obs.Tracer(clock=clk, epoch=0.0)
    with tr.activate():
        f(jnp.ones(8))               # eager: the span fires
    (e,) = tr.events
    assert (e["name"], e["cat"]) == ("encode/b0", "encode")
    f(jnp.ones(8))                   # NULL tracer: no span anywhere
    assert len(tr.events) == 1


def test_spans_land_on_the_profiler_clock(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData
    tr = obs.Tracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("obs-outer", cat="input"):
            with tr.span("obs-inner", cat="input"):
                pass
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = {e.name for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:")
            for line in p.lines for e in line.events}
    assert {"obs-outer", "obs-inner"} <= host
    assert obtrace.validate(tr) == 2


def test_compile_counts_by_function():
    import jax
    import jax.numpy as jnp
    reg = obs.compile_counts()
    assert obs.compile_counts() is reg       # one listener per process

    def obs_probe_fn(x):
        return x * 3

    before = reg.snapshot()["counters"].get("compiles/jit(obs_probe_fn)", 0)
    f = jax.jit(obs_probe_fn)
    f(jnp.ones(3))
    f(jnp.ones(3))                   # cached: no second compile
    f(jnp.ones(4))                   # a new shape compiles again
    after = reg.snapshot()["counters"]["compiles/jit(obs_probe_fn)"]
    assert after - before == 2


def test_stream_compiles_its_scan_every_step():
    # the eager lax.scan over a fresh closure in LMStream._tokens compiles
    # one program each call: the harness's compiles_per_step of 1.0
    from repro.data import LMStream
    reg = obs.compile_counts()
    s = LMStream(vocab_size=64, seq_len=8, global_batch=2)
    s.global_batch_at(0)
    before = reg.snapshot()["counters"]
    for step in (1, 2, 3):
        s.global_batch_at(step)
    after = reg.snapshot()["counters"]
    grew = {k: v - before.get(k, 0) for k, v in after.items()
            if v > before.get(k, 0)}
    assert grew == {"compiles/jit(scan)": 3}


def test_worker_batch_spans_the_stream_and_the_reshape():
    from repro.data import LMStream
    from repro.launch import train
    spec = RunSpec(smoke=True, batch=4, seq=8)
    spec = dataclasses.replace(
        spec, cluster=dataclasses.replace(spec.cluster, p=2))
    tr = obs.Tracer()
    with tr.activate():
        b = train.worker_batch(LMStream(vocab_size=64, seq_len=8,
                                        global_batch=4), 0, spec)
    assert b["tokens"].shape == (2, 2, 8)
    assert [(e["name"], e["cat"]) for e in tr.events] == [
        ("stream", "input"), ("reshape", "input")]


# ---------------------------------------------------------------------------
# Metrics + trace@2
# ---------------------------------------------------------------------------


def test_metrics_registry_and_histogram():
    m = obs.Metrics()
    m.counter("bytes").inc(10)
    m.counter("bytes").inc(5)          # get-or-create: same instrument
    m.gauge("ratio").set(2.5)
    for v in [1.0, 2.0, 3.0, 4.0]:
        m.histogram("t").observe(v)
    snap = m.snapshot()
    assert snap["counters"]["bytes"] == 15
    assert snap["gauges"]["ratio"] == 2.5
    h = snap["histograms"]["t"]
    assert h["count"] == 4 and h["min"] == 1.0 and h["max"] == 4.0
    assert h["p50"] == 2.0 and h["mean"] == pytest.approx(2.5)
    # zero-observation instruments export the FULL key set (all null),
    # so downstream JSON consumers stay schema-stable and never divide
    # by a zero count
    empty = obs.Metrics().histogram("e").summary()
    assert empty == {"count": 0, "mean": None, "min": None, "max": None,
                     "p50": None, "p90": None, "p95": None, "p99": None}
    snap = obs.Metrics()
    snap.histogram("never")            # instrument exists, no samples
    s = snap.snapshot()
    assert s["histograms"]["never"]["count"] == 0
    assert json.dumps(s)               # NaN-free, serializable


def test_trace2_jsonl_roundtrip(tmp_path):
    recs = [{"step": i, "t_step": 0.1, "rounds": 2, "bytes": 100.0,
             "warmup": i == 0} for i in range(3)]
    doc = obs.trace2_doc(model={"p": 2}, records=recs,
                         provenance={"schema": "x"})
    assert doc["schema"] == obs.TRACE2_SCHEMA
    p = str(tmp_path / "t.jsonl")
    obs.dump(doc, p)
    back = obs.load_jsonl(p)
    assert back["records"] == recs and back["model"] == {"p": 2}
    # calibrate's loader routes .jsonl through the same reassembly
    assert calibrate.load_trace(p) == recs


def test_calibrate_warmup_tags_beat_planted_outlier():
    """Regression for the warmup skew: a tagged jit-compiling first step
    with a wildly outlying t_step must NOT pollute the fit even with
    drop_first=0 — the trace@2 tags are authoritative."""
    planted = dict(alpha=2e-3, beta=4e-9, t_compute=0.05)
    doc = calibrate.synthetic_trace(
        cells=[(2, 1e5), (8, 1e5), (2, 8e5)], steps=4, **planted)
    recs = [dict(r) for r in doc["records"]]
    recs[0]["t_step"] = 40.0           # the jit-compile outlier
    recs[0]["warmup"] = True
    cal = calibrate.fit([recs], drop_first=0)
    assert cal.alpha == pytest.approx(planted["alpha"], rel=1e-5)
    assert cal.beta == pytest.approx(planted["beta"], rel=1e-5)
    assert cal.t_compute == pytest.approx(planted["t_compute"], rel=1e-5)
    assert cal.n_records == len(recs) - 1
    # contrast: the same outlier untagged DOES poison a drop_first=0 fit
    del recs[0]["warmup"]
    bad = calibrate.fit([recs], drop_first=0)
    assert abs(bad.t_compute - planted["t_compute"]) > 0.1


def test_provenance_stamp_and_runspec_hash():
    p = obs.provenance(RunSpec())
    for key in ("schema", "jax", "backend", "hostname", "platform",
                "python", "git_rev", "runspec_sha256"):
        assert key in p
    assert p["schema"] == "repro.obs/provenance@1"
    assert obs.runspec_hash(RunSpec()) == obs.runspec_hash(RunSpec())
    changed = dataclasses.replace(RunSpec(), seed=123)
    assert obs.runspec_hash(changed) != obs.runspec_hash(RunSpec())
    json.dumps(p)   # must be serializable as-is


# ---------------------------------------------------------------------------
# Runtime-layer structured events (failure injection)
# ---------------------------------------------------------------------------


def test_runtime_failure_injection_emits_instants():
    from repro.runtime.elastic import initial_plan, replan
    from repro.runtime.heartbeat import HeartbeatMonitor
    from repro.runtime.straggler import DeadlinePolicy

    clk = FakeClock()
    tr = obs.Tracer(clock=clk, epoch=0.0)
    with tr.activate():
        hb = HeartbeatMonitor(range(4), clock=clk)
        clk.t = 1.5
        for w in (0, 1, 2):
            hb.beat(w)
        clk.t = 2.0                       # worker 3 silent past timeout=1
        assert hb.dead(1.0) == {3}
        assert hb.dead(1.0) == {3}        # still dead — but only ONE instant

        plan = replan(initial_plan(4), failed={3}, joined=())
        pol = DeadlinePolicy(factor=3.0, max_drop_frac=0.5)
        pol.observe([1.0, 1.0, 1.0])
        pol.mask([1.0, 1.0, 10.0])        # worker at index 2 straggles

    doc = tr.to_chrome()
    dead = obtrace.instants(doc, "heartbeat.dead")
    assert len(dead) == 1 and dead[0]["args"]["worker"] == 3
    assert dead[0]["args"]["silence"] == pytest.approx(2.0)
    rp = obtrace.instants(doc, "elastic.replan")
    assert len(rp) == 1 and rp[0]["args"]["failed"] == [3]
    assert rp[0]["args"]["generation"] == plan.generation
    drops = obtrace.instants(doc, "straggler.drop")
    assert len(drops) == 1 and drops[0]["args"]["dropped"] == [2]
    # outside the activation everything is a no-op again
    assert hb.dead(0.1) and len(tr.events) == len(doc["traceEvents"]) - 1


def test_heartbeat_rebeat_rearms_the_instant():
    from repro.runtime.heartbeat import HeartbeatMonitor
    clk = FakeClock()
    tr = obs.Tracer(clock=clk, epoch=0.0)
    hb = HeartbeatMonitor([0], clock=clk)
    with tr.activate():
        clk.t = 2.0
        hb.dead(1.0)
        hb.beat(0)                         # recovers...
        clk.t = 4.0
        hb.dead(1.0)                       # ...dies again: a fresh instant
    assert len(obtrace.instants(tr.to_chrome(), "heartbeat.dead")) == 2


def test_sim_fault_injection_lands_in_exported_trace(tmp_path):
    """A mid-run failure injected through the event-loop sim must surface
    as the SAME structured events a real runtime emits: an
    ``elastic.replan`` instant (and stall spans) in the exported trace."""
    from repro.sim.cluster import SimConfig, simulate
    from repro.sim.traces import FaultTrace, TraceEvent

    cfg = SimConfig(p=4, d=100_000, method="gs-sgd", buckets=2, steps=6)
    res = simulate(cfg, FaultTrace(events=(TraceEvent(2, "fail", 1),)))
    assert res.replans, "fault trace must force a replan"
    tr = res.to_tracer()
    path = str(tmp_path / "sim.json")
    doc = tr.save(path, spec={"p": 4}, source="sim")
    assert obtrace.validate(doc) > 0
    rp = obtrace.instants(doc, "elastic.replan")
    assert rp and rp[0]["args"]["failed"] == [1]
    assert rp[0]["args"]["p"] == 3
    assert obtrace.phase_totals(doc)["stall"] > 0   # the detection wait
    steps = obtrace.spans(doc, cat="step")
    assert len(steps) == cfg.steps
    assert all(s["args"]["warmup"] is False for s in steps)


# ---------------------------------------------------------------------------
# Train integration: probe spans, trace@2, zero overhead off
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def train_runs(tmp_path_factory):
    """One untraced + one fully-traced run of the same tiny config."""
    from repro.launch.train import main as train_main
    tmp = tmp_path_factory.mktemp("obs")
    trace_p = str(tmp / "trace.json")
    json_p = str(tmp / "steps.json")
    plain = train_main(list(TRAIN_ARGV))
    traced = train_main(TRAIN_ARGV + ["--trace", trace_p,
                                      "--json", json_p])
    return plain, traced, trace_p, json_p


def test_tracing_off_is_byte_identical(train_runs):
    plain, traced, _, _ = train_runs
    # the acceptance pin: --trace/--json must not perturb the jitted step
    # (the probe's output is discarded; NULL tracing changes no jaxpr)
    assert plain["history"] == traced["history"]


def test_train_trace_has_probe_phases_and_step_spans(train_runs):
    _, _, trace_p, _ = train_runs
    doc = obtrace.load(trace_p)
    assert doc["source"] == "train"
    assert obtrace.validate(doc) > 0
    assert doc["spec"]["cluster"]["p"] == 2
    assert doc["provenance"]["runspec_sha256"]
    steps = obtrace.spans(doc, cat="step")
    assert len(steps) == STEPS
    warm = {s["args"]["step"]: s["args"]["warmup"] for s in steps}
    assert warm[0] is True and not any(warm[i] for i in range(1, STEPS))
    assert len(obtrace.spans(doc, cat="probe")) == 1
    totals = obtrace.phase_totals(doc)
    for ph in ("backward", "encode", "comm", "recover", "optimizer"):
        assert totals.get(ph, 0.0) > 0.0, f"missing phase {ph}"
    # per-bucket pipeline spans, one per bucket
    assert len(obtrace.bucket_durations(doc, "encode", "encode/b")) == 2
    assert len(obtrace.bucket_durations(doc, "comm", "allreduce/b")) == 2
    assert len(obtrace.bucket_durations(doc, "recover", "recover/b")) == 2
    assert obtrace.instants(doc, "ready/b0")


def test_train_trace2_counts_compiles_by_function(train_runs):
    _, _, _, json_p = train_runs
    with open(json_p) as f:
        counters = json.load(f)["metrics"]["counters"]
    assert counters["compiles/jit(train_step)"] == 1   # one step program
    assert counters["compiles/jit(scan)"] >= STEPS     # LMStream, per step


def test_train_trace2_superset_roundtrips_through_calibrate(train_runs):
    _, _, _, json_p = train_runs
    with open(json_p) as f:
        doc = json.load(f)
    assert doc["schema"] == obs.TRACE2_SCHEMA
    assert doc["provenance"]["runspec_sha256"]
    assert doc["metrics"]["counters"]["bytes_wire"] > 0
    assert doc["metrics"]["counters"]["bytes_wire/b0"] > 0   # per bucket
    assert doc["metrics"]["counters"]["bytes_wire/b1"] > 0
    assert doc["metrics"]["histograms"]["t_step"]["count"] == STEPS - 1
    assert 0.0 <= doc["metrics"]["gauges"]["recovery_error_probe"] < 1.0
    assert doc["metrics"]["gauges"]["hidden_comm"] >= 0
    assert "step_time" in doc["predicted"]
    for i, r in enumerate(doc["records"]):
        for key in ("step", "t_step", "rounds", "bytes", "loss"):  # trace@1
            assert key in r
        assert r["warmup"] is (i == 0)
        assert r["grad_norm"] > 0 and r["ef_residual_norm"] >= 0
        assert r["bytes_wire"] == r["bytes"] * 2
        assert r["compression_ratio"] > 1
    recs = calibrate.load_trace(json_p)    # consumed unchanged
    assert len(recs) == STEPS
    assert calibrate._drop_warmup(recs, 0)[0]["step"] == 1


def test_sim_and_train_traces_share_one_schema(train_runs, tmp_path):
    from repro.launch.simulate import main as sim_main
    _, _, trace_p, _ = train_runs
    sim_p = str(tmp_path / "sim_trace.json")
    sim_main(["--p", "2", "--d", "100000", "--method", "gs-sgd",
              "--buckets", "2", "--bwd-chunks", "2", "--steps", "3",
              "--trace", sim_p])
    t_doc = obtrace.load(trace_p)
    s_doc = obtrace.load(sim_p)
    assert sorted(t_doc) == sorted(s_doc)          # same top-level keys
    for doc in (t_doc, s_doc):
        for e in doc["traceEvents"]:
            if e["ph"] == "X":
                assert sorted(e) == ["args", "cat", "dur", "name", "ph",
                                     "pid", "tid", "ts"]
        cats = {e["cat"] for e in doc["traceEvents"] if e["ph"] == "X"}
        for ph in ("backward", "encode", "comm", "recover"):
            assert ph in cats, f"{doc['source']} trace missing {ph}"


# ---------------------------------------------------------------------------
# Overlap audit
# ---------------------------------------------------------------------------


def test_overlap_audit_sim_self_check(tmp_path):
    """A jitter-free sim trace must reproduce its own pricing oracle:
    per-phase deltas ~0 and the promised overlap exactly realized
    (predict_step == one jitter-free simulated step is pinned)."""
    from benchmarks.overlap_audit import audit_trace, check
    from repro.launch.simulate import main as sim_main
    p = str(tmp_path / "sim_trace.json")
    sim_main(["--p", "4", "--d", "1000000", "--method", "gs-sgd",
              "--buckets", "4", "--bwd-chunks", "2", "--steps", "4",
              "--compute-jitter", "0", "--trace", p])
    a = audit_trace(p)
    assert a["source"] == "sim"
    for ph in ("encode", "comm", "recover"):
        assert a["phase_deltas"][ph]["measured"] == pytest.approx(
            a["phase_deltas"][ph]["predicted"], rel=1e-6, abs=1e-12)
    assert a["measured"]["step_time"] == pytest.approx(
        a["scheduled_step"], rel=1e-6)
    if a["serial_step"] - a["scheduled_step"] > 1e-9:
        assert a["realization_ratio"] == pytest.approx(1.0, abs=1e-3)
    assert check(a, 0.05) == []


def test_overlap_audit_on_train_trace(train_runs, tmp_path):
    from benchmarks.overlap_audit import audit_trace, check, main
    _, _, trace_p, _ = train_runs
    a = audit_trace(trace_p)
    assert a["source"] == "train"
    assert a["measured"]["step_time"] > 0
    for ph in ("backward", "encode", "comm", "recover"):
        d = a["phase_deltas"][ph]
        assert np.isfinite(d["measured"]) and np.isfinite(d["predicted"])
    ms = a["measured_schedule"]
    assert ms is not None and ms["pipelined"] <= ms["serial"] + 1e-12
    assert check(a, 0.0) == []       # measured traces are report-only
    out_p = str(tmp_path / "BENCH_obs.json")
    res = main([trace_p, "--tolerance", "10.0", "--out", out_p])
    assert res["audits"][0]["trace"] == trace_p
    with open(out_p) as f:
        assert json.load(f)["schema"] == "repro.obs/bench@1"
