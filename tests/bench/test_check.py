"""The comparison that decides ``correct``, on hand-made readings."""

import math

from bench import check

REF = {"losses": [8.0, 7.0, 6.0],
       "grad_norms": {"a": 1.0, "b": 2.0, "c": 4.0, "gain": 1e-6},
       "change_norms": {"a": 0.5, "b": 0.5, "c": 1.0, "gain": 1e-9}}


def test_equal_readings_have_no_gap():
    assert check.gaps(REF, REF) == {"loss_gap": 0.0, "grad_gap": 0.0,
                                    "change_gap": 0.0}


def test_gaps_take_the_worst_step_and_leaf():
    prog = {"losses": [8.0, 7.07, 6.0],
            "grad_norms": {"a": 1.1, "b": 2.0, "c": 4.0, "gain": 3e-6},
            "change_norms": {"a": 0.5, "b": 0.55, "c": 1.0, "gain": 5e-9}}
    g = check.gaps(prog, REF)
    assert math.isclose(g["loss_gap"], 0.01)
    # median leaf gradient norm 1.5: leaf a's 0.1 is measured against it;
    # the gain's tiny norm gap too
    assert math.isclose(g["grad_gap"], 0.1 / 1.5)
    # the gain leaf has no gradient (under 1e-3 of the median): its
    # change is round-off and is left out
    assert math.isclose(g["change_gap"], 0.05 / 0.5)


def test_unchanged_state_reads_one():
    prog = dict(REF, change_norms={k: 0.0 for k in REF["change_norms"]})
    assert check.gaps(prog, REF)["change_gap"] == 1.0


def test_judge_against_limits():
    ok, out = check.judge({"loss_gap": 1e-6, "grad_gap": 2e-3,
                           "change_gap": math.nan},
                          {"loss_gap": 1e-5, "grad_gap": 1e-3,
                           "change_gap": None})
    assert not ok
    assert out["grad_gap"] == {"value": 2e-3, "limit": 1e-3}
    ok, _ = check.judge({"loss_gap": 1e-6, "grad_gap": 1e-4,
                         "change_gap": math.nan},
                        {"loss_gap": 1e-5, "grad_gap": 1e-3,
                         "change_gap": None})
    assert ok
    ok, _ = check.judge({"loss_gap": math.inf, "grad_gap": 0.0,
                         "change_gap": 0.0},
                        {"loss_gap": 1e-5, "grad_gap": 1e-3,
                         "change_gap": 1e-3})
    assert not ok


def test_gaps_take_the_worst_worker():
    stale = dict(REF["change_norms"], b=0.0)    # worker 1's b never moved
    prog = dict(REF, grad_norms=[REF["grad_norms"]] * 2,
                change_norms=[REF["change_norms"], stale])
    g = check.gaps(prog, REF)
    assert g["grad_gap"] == 0.0
    assert g["change_gap"] == 1.0


def test_feed_faults_count_repeats_and_misaligned_labels():
    import numpy as np
    seq = np.arange(3 * 4 * 9).reshape(3, 4, 9)
    fed = [{"tokens": s[:, :-1], "labels": s[:, 1:]} for s in seq]
    assert check.feed_faults(fed) == 0
    repeated = fed[:2] + [fed[0]]
    assert check.feed_faults(repeated) == 4
    bad = [dict(fed[0], labels=fed[0]["tokens"])] + fed[1:]
    assert check.feed_faults(bad) == 4 * 7
