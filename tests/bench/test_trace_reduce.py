"""The trace reduction, pinned on hand-made events and on two window steps
recorded on a v5e chip (``data/``: one step of each cell under
``jax.profiler``, read with ``trace_reduce.events_of``, trimmed to the
step's host spans and written with ``trace_reduce.save_events``)."""

import os

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_interval_arithmetic():
    assert tr.union([(3, 4), (0, 2), (1, 2.5)]) == [[0, 2.5], [3, 4]]
    assert tr.length([[0, 2.5], [3, 4]]) == 3.5
    assert tr.subtract([[0, 10]], [[1, 2], [4, 6], [9, 12]]) == \
        [[0, 1], [2, 4], [6, 9]]
    assert tr.subtract([[0, 1], [5, 6]], []) == [[0, 1], [5, 6]]


@pytest.mark.parametrize("name, cls", [
    ("vmap_jit_sketch_encode__.2", "encode"),
    ("jit_sketch_encode_.1", "encode"),
    ("sort.12", "sort"),
    ("all-reduce.4", "collective"),
    ("collective-permute-done.1", "collective"),
    ("all-gather-start", "collective"),
    ("while.146", "container"),
    ("fusion.7", "other"),
    ("bitcast_select_fusion.3", "other")])
def test_classify(name, cls):
    assert tr.classify(name) == cls


def test_op_name_from_hlo_text():
    assert tr.op_name("%sort.25 = (f32[4194304]{0}, s32[4194304]{0}) "
                      "sort(f32[4194304]{0} %fusion.332)") == "sort.25"
    assert tr.op_name("jit_step(123)") == "jit_step(123)"


def test_reduce_events_by_hand():
    # window 0..10 s from the host spans; one device:
    #   encode 1..3, sort 2..4 (overlaps), all-reduce 5..6 alone,
    #   all-gather 7..9 under a fusion 8..9, an op 12..13 outside.
    #   a while loop 3.5..4.5 around the sort's tail (busy, no class).
    ops = "XLA Ops"
    devices = {0: [(ops, "jit_sketch_encode_.1", 1.0, 3.0),
                   (ops, "sort.1", 2.0, 4.0),
                   (ops, "while.1", 3.5, 4.5),
                   (ops, "all-reduce.1", 5.0, 6.0),
                   (ops, "all-gather.1", 7.0, 9.0),
                   (ops, "fusion.1", 8.0, 9.0),
                   (ops, "fusion.2", 12.0, 13.0)]}
    spans = [("input", 0.0, 1.0), ("dispatch", 1.0, 1.5),
             ("sync", 1.5, 10.0), ("other", -5.0, 20.0)]
    r = tr.reduce_events(devices, spans)
    assert r["window_s"] == 10.0
    assert r["busy_s"] == 3.5 + 1.0 + 2.0          # 1..4.5, 5..6, 7..9
    assert r["busiest"]["class_s"] == {"encode": 2.0, "sort": 2.0,
                                       "collective": 3.0, "other": 1.0}
    assert r["collective_exposed_s_max"] == 2.0     # 5..6 and 7..8
    assert dict(r["top_ops"]) == {
        "encode:jit_sketch_encode_.1": 2.0, "sort:sort.1": 2.0,
        "collective:all-gather.1": 2.0, "collective:all-reduce.1": 1.0,
        "other:fusion.1": 1.0}
    assert [v for _, v in r["top_ops"]] == [2.0, 2.0, 2.0, 1.0, 1.0]
    # idle: 0..1 (input), 4.5..5, 6..7, 9..10 (sync)
    assert sorted(r["idle_gaps"], key=lambda g: (g[1], g[0])) == [
        ["sync", 0.5], ["input", 1.0], ["sync", 1.0], ["sync", 1.0]]


def test_busiest_device_and_mean():
    ops = "XLA Ops"
    devices = {0: [(ops, "fusion.1", 0.0, 1.0)],
               1: [(ops, "fusion.1", 0.0, 3.0),
                   (ops, "collective-permute-start.1", 3.0, 3.1),
                   ("Async XLA Ops", "collective-permute-start.1", 3.0,
                    4.0),
                   (ops, "collective-permute-done.1", 3.9, 4.0)]}
    r = tr.reduce_events(devices, [("input", 0.0, 4.0)])
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((1.0 + 3.2) / 2)
    assert r["busiest"]["busy_s"] == pytest.approx(3.2)
    assert r["class_s_max"]["collective"] == 1.0    # in flight 3..4
    assert r["collective_exposed_s_max"] == 1.0


def test_saved_events_round_trip(tmp_path):
    devices = {0: [("XLA Ops", "fusion.1", 0.5, 1.5)]}
    spans = [("input", 0.0, 2.0)]
    path = str(tmp_path / "t.json.gz")
    tr.save_events(path, devices, spans)
    assert tr.load_events(path) == (devices, spans)


def recorded(name):
    return tr.reduce_events(*tr.load_events(os.path.join(
        DATA, f"v5e-musicgen-{name}-step.json.gz")))


def test_recorded_gs_sgd_step():
    # one window step of musicgen-gs-sgd-p2 on a v5e chip: the Pallas
    # encode is 71% of the step, HEAVYMIX's sorts 1%, no collectives
    r = recorded("gs-sgd-p2")
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(14.590806001, abs=1e-9)
    assert r["busy_s"] == pytest.approx(14.459937781, abs=1e-9)
    c = r["busiest"]["class_s"]
    assert c["encode"] == pytest.approx(10.306395439, abs=1e-9)
    assert c["sort"] == pytest.approx(0.140435537, abs=1e-9)
    assert c["collective"] == 0
    assert r["top_ops"][0] == ["encode:vmap_jit_sketch_encode__.2",
                               pytest.approx(10.306395439, abs=1e-9)]
    assert r["idle_gaps"][0] == ["input", pytest.approx(0.117400159,
                                                        abs=1e-9)]


def test_recorded_dense_step():
    # one window step of musicgen-dense-p2: the device works 0.160 s of a
    # 0.299 s step; the longest gap is the host building the next batch
    r = recorded("dense-p2")
    assert r["window_s"] == pytest.approx(0.299361042, abs=1e-9)
    assert r["busy_s"] == pytest.approx(0.160428355, abs=1e-9)
    c = r["busiest"]["class_s"]
    assert c["encode"] == 0 and c["collective"] == 0
    assert c["other"] == pytest.approx(0.160403838, abs=1e-9)
    assert r["idle_gaps"][0] == ["input", pytest.approx(0.125126253,
                                                        abs=1e-9)]
