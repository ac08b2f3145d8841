"""Device time by phase of the train step (``bench/phases.py``): the
name-stack parser, the reduction on hand-made 5-tuple events, the
readers' silence where the program names no phase, one capture at a tiny
size on the CPU (which has no TPU plane to read), and one scoped step
recorded on a v5e chip (``data/``)."""

import os

import pytest

from bench import cell as cells
from bench import phases as ph
from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

@pytest.mark.parametrize("op_name, phase", [
    ("jit(train_step)/vmap(jvp(forward))/while/body/closed_call/dot_general",
     "forward"),
    ("jit(train_step)/vmap(transpose(jvp(forward)))/while/body/closed_call/"
     "checkpoint/rematted_computation/dot_general", "backward"),
    ("jit(step)/transpose(jvp(loss))/forward/mul", "backward"),
    ("jit(train_step)/vmap(encode)/jit(sketch_encode)/pallas_call", "encode"),
    ("jit(train_step)/vmap(comm)/reduce_sum", "comm"),
    ("jit(train_step)/vmap(recover)/while/body/closed_call/recover/decode/"
     "jit(take_along_axis)/gather", "recover/decode"),
    ("jit(train_step)/vmap(recover/decode)/while/body/closed_call/"
     "recover/select/top_k", "recover/select"),
    ("jit(train_step)/vmap(recover)/recover/second_round/scatter",
     "recover/second_round"),
    ("jit(train_step)/vmap(recover)/broadcast_in_dim", "recover"),
    ("jit(train_step)/vmap(optimizer)/mul", "optimizer"),
    ("jit(train_step)/encode/transpose(jvp(forward))/mul", "backward"),
    ("jit(train_step)/vmap()/add", None),
    ("jit(train_step)/cos", None),
    ("", None),
])
def test_phase_of_name_stack(op_name, phase):
    assert ph.phase_of(op_name) == phase


def test_phases_are_the_programs_scopes():
    from repro import obs
    assert set(ph.phases()) == set(obs.SCOPES) | {"recover"}


def test_stale_scopes_compares_the_lowered_names_with_the_compiled():
    lowered = """module @jit_train_step {
  %0 = stablehlo.add %a, %b : tensor<4xf32> loc(#loc3)
  %1 = stablehlo.sort %0 : tensor<4xf32> loc(#loc4)
}
#loc3 = loc("jit(train_step)/vmap(encode)/add"(#loc1))
#loc4 = loc("jit(train_step)/vmap(recover)/recover/select/top_k"(#loc2))
"""
    def compiled(*op_names):
        return "\n".join(f'  %op.{i} = f32[4]{{0}} add(%p), metadata={{'
                         f'op_name="{n}"}}' for i, n in enumerate(op_names))
    enc = "jit(train_step)/vmap(encode)/add"
    sel = "jit(train_step)/vmap(recover)/recover/select/top_k"
    assert ph.stale_scopes(lowered, compiled(enc, sel)) == set()
    # a scope added since the cache was filled: the old names lack it
    assert ph.stale_scopes(lowered, compiled(
        enc, "jit(train_step)/vmap(recover)/top_k")) == {"recover/select",
                                                          "recover"}
    # a scope taken out: the old names still carry it
    assert ph.stale_scopes(lowered, compiled(
        enc, sel, "jit(train_step)/vmap(optimizer)/mul")) == {"optimizer"}


def test_scope_paths_from_hlo_text():
    text = """HloModule jit_train_step, is_scheduled=true

%fused_computation (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  ROOT %gather.1 = f32[4]{0} gather(%param_0), metadata={op_name="jit(train_step)/vmap(recover)/recover/decode/gather" stack_frame_id=3}
}

ENTRY %main.9 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0), metadata={op_name="state"}
  %fusion.329 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(train_step)/vmap(recover)/recover/decode/gather" stack_frame_id=3}
  ROOT %sort.25 = f32[4]{0} sort(%fusion.329), dimensions={0}, metadata={op_name="jit(train_step)/vmap(recover)/recover/select/top_k"}
  %copy.1 = f32[4]{0} copy(%p)
}
"""  # noqa: E501
    paths = ph.scope_paths(text)
    assert ph.module_name(text) == "jit_train_step"
    assert paths["fusion.329"].endswith("recover/decode/gather")
    assert ph.phase_of(paths["sort.25"]) == "recover/select"
    assert paths["p"] == "state" and "copy.1" not in paths


def hand_made():
    # window 0..10 s; one device. The phases' leaf ops; a while loop
    # (busy, no phase of its own) around a select op; an op with no
    # name stack; a 4-tuple op; a backward op; an op outside the window.
    ops = "XLA Ops"
    fwd = "jit(s)/vmap(jvp(forward))/dot_general"
    devices = {0: [
        (ops, "fusion.1", 0.0, 1.0, fwd),
        (ops, "fusion.2", 1.0, 3.0,
         "jit(s)/vmap(transpose(jvp(forward)))/dot_general"),
        (ops, "sketch_encode.1", 3.0, 6.0,
         "jit(s)/vmap(encode)/jit(sketch_encode)/pallas_call"),
        (ops, "while.1", 6.0, 8.0, "jit(s)/vmap(recover)/while"),
        (ops, "fusion.329", 6.0, 7.5,
         "jit(s)/vmap(recover)/while/body/recover/decode/gather"),
        (ops, "sort.25", 7.5, 7.75,
         "jit(s)/vmap(recover/decode)/while/body/recover/select/top_k"),
        (ops, "dynamic-update-slice.14", 8.0, 8.5, ""),
        (ops, "fusion.6", 8.5, 9.0),
        ("Async XLA Ops", "all-reduce-start.1", 8.5, 9.5,
         "jit(s)/vmap(comm)/psum"),
        (ops, "fusion.9", 12.0, 13.0, fwd)]}
    spans = [("input", 0.0, 0.5), ("dispatch", 0.5, 1.0),
             ("sync", 1.0, 10.0), ("stream", 0.0, 0.4)]
    return devices, spans


def test_reduce_phases_by_hand():
    r = ph.reduce_phases(*hand_made())
    assert r["window_s"] == 10.0
    assert r["busy_s"] == 9.0
    p = r["phase_s"]
    assert p["forward"] == 1.0 and p["backward"] == 2.0   # transpose
    assert p["encode"] == 3.0
    assert p["recover/decode"] == 1.5
    assert p["recover/select"] == 0.25                    # innermost wins
    assert p["recover"] == 0.0      # the loop is busy time, not a leaf
    assert p["comm"] == 0.0         # in flight on the async line only
    assert r["unscoped_s"] == pytest.approx(1.25)   # loop tail, 8..9
    assert sum(p.values()) + r["unscoped_s"] == pytest.approx(r["busy_s"])
    assert r["idle_gaps"] == [["sync", 1.0]]


def test_idle_gap_named_by_innermost_span():
    # the first gap lies under both "input" and its child "stream"
    devices = {0: [("XLA Ops", "fusion.1", 0.6, 2.0, "")]}
    spans = [("input", 0.0, 1.0), ("stream", 0.0, 0.6),
             ("reshape", 0.6, 1.0), ("sync", 1.0, 3.0)]
    r = ph.reduce_phases(devices, spans)
    assert r["idle_gaps"] == [["sync", 1.0], ["stream", 0.6]]


def test_gap_name_prefers_the_inner_span_that_covers_it():
    spans = [("input", 0.0, 1.0), ("stream", 0.2, 0.8)]
    assert ph._gap_name([0.3, 0.7], spans) == "stream"
    assert ph._gap_name([0.0, 1.0], spans) == "input"
    assert ph._gap_name([2.0, 3.0], spans) == "none"


def test_five_tuples_round_trip_and_four_tuples_still_reduce(tmp_path):
    devices, spans = hand_made()
    path = str(tmp_path / "t.json.gz")
    tr.save_events(path, devices, spans)
    assert tr.load_events(path) == (devices, spans)
    # the harness's own reduction takes the 5-tuples' first four fields
    four = {d: [e[:4] for e in ev] for d, ev in devices.items()}
    assert tr.reduce_events(four, spans)["busy_s"] == 9.0
    # a trace without name stacks reduces to no phase at all
    r = ph.reduce_phases(four, spans)
    assert sum(r["phase_s"].values()) == 0.0 and r["unscoped_s"] == 9.0


class _Event:
    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns, self.duration_ns = (name, start_ns,
                                                      duration_ns)


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def test_events_of_maps_only_the_step_modules_ops():
    # fusion.1 runs in the step's module and in another program (the
    # batch build's); only the step's instance takes the step's name stack
    prof = type("P", (), {"planes": [
        _Plane("/device:TPU:0", [
            _Line("XLA Modules", [_Event("jit_train_step(7)", 100, 900),
                                  _Event("jit_scan(8)", 2000, 100)]),
            _Line("XLA Ops", [_Event("%fusion.1 = f32[2] fusion()", 200, 50),
                              _Event("%fusion.1 = f32[2] fusion()", 2010,
                                     50)])]),
        _Plane("/host:CPU", [_Line("python", [
            _Event("input", 0, 100), _Event("stream", 0, 50),
            _Event("other", 0, 10)])])]})()
    paths = {"fusion.1": "jit(train_step)/vmap(optimizer)/mul"}
    devices, spans = ph.events_of(prof, 1, paths, "jit_train_step",
                                  {"stream"})
    assert [e[4] for e in devices[0]] == [paths["fusion.1"], ""]
    assert [s[0] for s in spans] == ["input", "stream"]


@pytest.fixture
def no_capture(monkeypatch):
    """A build without the scopes: ``profile_step`` finds no phase."""
    ph._CACHE.clear()
    monkeypatch.setattr(ph, "profile_step", lambda *a: (None, None, 0))
    yield
    ph._CACHE.clear()


@pytest.mark.parametrize("name", [
    "forward_s_per_step", "backward_s_per_step", "optimizer_s_per_step",
    "allreduce_s_per_step", "heavymix_decode_s_per_step",
    "heavymix_select_s_per_step", "second_round_s_per_step",
    "unscoped_share"])
def test_readers_read_nothing_without_scopes(no_capture, name):
    run = {"cell": None, "chips": 1}
    assert cells.metric_reader(name)(run) is None


def test_readers_share_one_capture(monkeypatch):
    ph._CACHE.clear()
    calls = []

    def fake(cell, chips):
        calls.append(chips)
        return hand_made() + (1,)

    monkeypatch.setattr(ph, "profile_step", fake)
    run = {"cell": None, "chips": 1}
    got = {n: cells.metric_reader(n)(run) for n in (
        "forward_s_per_step", "backward_s_per_step", "allreduce_s_per_step",
        "heavymix_decode_s_per_step", "heavymix_select_s_per_step",
        "unscoped_share")}
    assert calls == [1]
    assert got == {"forward_s_per_step": 1.0, "backward_s_per_step": 2.0,
                   "allreduce_s_per_step": None,   # no comm op ran
                   "heavymix_decode_s_per_step": 1.5,
                   "heavymix_select_s_per_step": 0.25,
                   "unscoped_share": pytest.approx(100 * 1.25 / 9.0)}
    ph._CACHE.clear()


def test_a_failed_capture_reads_nothing(monkeypatch, capsys):
    ph._CACHE.clear()

    def broken(*a):
        raise RuntimeError("planted")

    monkeypatch.setattr(ph, "profile_step", broken)
    assert ph.per_step({"cell": None, "chips": 1}, "forward") is None
    assert "phase capture failed" in capsys.readouterr().err
    ph._CACHE.clear()


def test_capture_on_the_cpu_builds_the_program_and_reads_nothing(
        root, capsys):
    # the tiny cell (conftest): the compiled step names its phases, one
    # step is profiled, and the CPU trace holds no TPU plane to read
    bench = cells.load_benchmark(root)
    cell = cells.resolve(bench, "tiny-gs", root)
    ph._CACHE.clear()
    assert ph.capture({"cell": cell, "chips": 1}) is None
    err = capsys.readouterr().err
    assert "compiles in the profiled step" in err
    assert "no TPU plane" in err
    ph._CACHE.clear()


def test_capture_refuses_a_step_with_stale_names(root, capsys,
                                                 monkeypatch):
    # a step from a compile cache filled before a scope moved: no step is
    # profiled and the readers read nothing
    bench = cells.load_benchmark(root)
    cell = cells.resolve(bench, "tiny-gs", root)
    monkeypatch.setattr(ph, "stale_scopes", lambda *a: {"optimizer"})
    ph._CACHE.clear()
    assert ph.capture({"cell": cell, "chips": 1}) is None
    err = capsys.readouterr().err
    assert "disagree on the phases ['optimizer']" in err
    assert "compiles in the profiled step" not in err
    ph._CACHE.clear()


def test_recorded_scoped_gs_sgd_step():
    # one step of musicgen-gs-sgd-p2 with the phase scopes, profiled on a
    # v5e chip (``python3 -m bench.phases --save``): the phases and the
    # unscoped rest sum to the busy time; the decode phase holds HEAVYMIX's
    # gather (fusion.329), the encode phase the kernel and its EF add
    devices, spans = tr.load_events(os.path.join(
        DATA, "v5e-musicgen-gs-sgd-p2-scoped-step.json.gz"))
    r = ph.reduce_phases(devices, spans)
    assert r["window_s"] == pytest.approx(14.631847388, abs=1e-9)
    assert r["busy_s"] == pytest.approx(14.459930892, abs=1e-9)
    p = r["phase_s"]
    assert p["encode"] == pytest.approx(10.310805314, abs=1e-9)
    assert p["recover/decode"] == pytest.approx(3.746716131, abs=1e-9)
    assert p["recover/select"] == pytest.approx(0.121810887, abs=1e-9)
    assert p["recover/second_round"] == pytest.approx(0.030902921, abs=1e-9)
    assert p["backward"] == pytest.approx(0.106728303, abs=1e-9)
    assert p["forward"] == pytest.approx(0.032647665, abs=1e-9)
    assert p["optimizer"] == pytest.approx(0.010336685, abs=1e-9)
    assert p["comm"] < 1e-6                 # a vmapped sum on one chip
    assert r["unscoped_s"] == pytest.approx(0.098758505, abs=1e-9)
    assert sum(p.values()) + r["unscoped_s"] == pytest.approx(
        r["busy_s"], abs=1e-9)
    assert r["idle_gaps"][0] == ["stream", pytest.approx(0.136172401,
                                                         abs=1e-9)]
    old = tr.reduce_events({d: [e[:4] for e in ev]
                            for d, ev in devices.items()}, spans)
    assert old["busy_s"] == r["busy_s"]
    ops = dict(old["top_ops"])
    assert p["recover/decode"] > ops["other:fusion.329"] == pytest.approx(
        3.707548181, abs=1e-9)
    enc = old["busiest"]["class_s"]["encode"]
    assert enc <= p["encode"] <= 1.01 * enc
