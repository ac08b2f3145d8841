"""The harness's refusals and its data-driven lookup, on the CPU."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import cell as cells
from bench import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return cells.load_benchmark(ROOT)


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "musicgen-gs-sgd-p2",
         "--seed", "2147483999", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())


def test_chip_count_mismatch_is_refused(tmp_path, capsys, bench):
    # the CPU backend shows one device; this copy of a cell asks for four
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    b = dict(bench, workloads=[dict(w, chips=4) for w in bench["workloads"]])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    rc = run.main(["--workload", bench["workloads"][0]["name"], "--seed",
                   "1", "--seconds", "1", "--trace", "0"],
                  root=str(tmp_path), require_tpu=False)
    out = capsys.readouterr()
    assert rc != 0
    assert "asks for 4 chips" in out.err
    assert out.out.strip() == ""


def test_every_name_resolves_to_its_file(bench):
    for w in bench["workloads"]:
        c = cells.resolve(bench, w["name"], ROOT)
        assert c.chips in (1, 4)
        assert cells.reference_module(c.config).Decoder.from_config(
            c.config)
        assert set(c.limits) >= {"loss_gap", "grad_gap", "change_gap"}
        assert c.end_to_end and c.per_layer
    for m in bench["per_layer"]:
        assert callable(cells.metric_reader(m["name"], ROOT))
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(ROOT, c["file"]))


def test_added_cell_resolves_without_code_edit(tmp_path, bench):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    traffic = tmp_path / "bench" / "traffic" / "dense-p2-b4s1536.json"
    with open(os.path.join(ROOT, "bench", "traffic",
                           "gs-sgd-p2-b8s1536.json")) as f:
        t = json.load(f)
    t.update(compressor="none", global_batch=4)
    del t["sketch"]
    traffic.write_text(json.dumps(t))
    (tmp_path / "bench" / "limits" / "musicgen-dense-p2-b4.json").write_text(
        json.dumps({"loss_gap": 1e-5, "grad_gap": 1e-4, "change_gap": 1e-4}))
    (tmp_path / "bench" / "metrics" / "steps_in_window.py").write_text(
        "def read(run):\n    return len(run['window']['steps'])\n")
    b = dict(bench)
    b["workloads"] = bench["workloads"] + [{
        "name": "musicgen-dense-p2-b4", "config": "musicgen-large-l1",
        "traffic": "dense-p2-b4s1536", "chips": 1, "why": "added"}]
    b["per_layer"] = bench["per_layer"] + [{
        "name": "steps_in_window", "unit": "steps", "better": "higher",
        "source": "host_clock", "layer": "launcher input",
        "moves": "tokens_per_s", "workloads": ["musicgen-dense-p2-b4"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    c = cells.resolve(cells.load_benchmark(str(tmp_path)),
                      "musicgen-dense-p2-b4", str(tmp_path))
    assert c.traffic["global_batch"] == 4
    assert "steps_in_window" in [m["name"] for m in c.per_layer]
    read = cells.metric_reader("steps_in_window", str(tmp_path))
    assert read({"window": {"steps": [1, 2, 3]}}) == 3


def test_benchmark_file_keeps_the_contract_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    for p in bench["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert e2e == {"tokens_per_s", "step_s_p95", "setup_s"}
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cell_names = {w["name"] for w in bench["workloads"]}
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cell_names)) <= cell_names
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
    for w in bench["workloads"]:
        assert any(cells.applies(m, w["name"]) for m in bench["per_layer"])
        assert len(w["why"]) <= 200
