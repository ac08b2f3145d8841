"""A tiny cell for whole harness runs on the CPU: musicgen's same-family
smoke configuration cut to one layer, two gs-SGD workers vmapped on one
device, with limits of its own (not a chip cell's)."""

import json
import os
import shutil

import pytest

from bench import run

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LIMITS = {"loss_gap": 1e-5, "grad_gap": 1e-4, "change_gap": 1e-4,
          "feed_faults": 0}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = tmp_path_factory.mktemp("bench_root")
    for d in ("configs", "traffic", "limits"):
        (r / "bench" / d).mkdir(parents=True)
    shutil.copytree(os.path.join(ROOT, "bench", "metrics"),
                    r / "bench" / "metrics")
    with open(os.path.join(ROOT, "bench", "configs",
                           "musicgen-large-l1.json")) as f:
        c = json.load(f)
    c.update(smoke=True, num_hidden_layers=1, hidden_size=64,
             num_attention_heads=4, num_key_value_heads=4,
             intermediate_size=128, vocab_size=64)
    (r / "bench" / "configs" / "tiny.json").write_text(json.dumps(c))
    with open(os.path.join(ROOT, "bench", "traffic",
                           "gs-sgd-p2-b8s1536.json")) as f:
        t = json.load(f)
    t.update(global_batch=4, seq=8)
    t["sketch"].update(width=8192, density=0.05)
    (r / "bench" / "traffic" / "tiny-gs.json").write_text(json.dumps(t))
    (r / "bench" / "limits" / "tiny-gs.json").write_text(json.dumps(LIMITS))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"] = [{"name": "tiny", "source": "test",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test"}]
    b["workloads"] = [{"name": "tiny-gs", "config": "tiny",
                       "traffic": "tiny-gs", "chips": 1, "why": "test"}]
    for m in b["per_layer"]:   # the gs-SGD metrics read the tiny cell too
        if "workloads" in m:
            m["workloads"] = ["tiny-gs"]
    (r / "BENCHMARK.json").write_text(json.dumps(b))
    return str(r)


@pytest.fixture
def harness(root, capsys):
    """Run the tiny cell with the chip check skipped and ``program_class``
    in the program's place; return the result line."""
    return lambda program_class, seed: result(root, capsys, program_class,
                                              seed)


def result(root, capsys, program_class, seed):
    rc = run.main(["--workload", "tiny-gs", "--seed", str(seed),
                   "--seconds", "0.2", "--trace", "0"], root=root,
                  require_tpu=False, hooks={"Program": program_class})
    out = capsys.readouterr()
    assert rc == 0
    doc = json.loads(out.out.strip().splitlines()[-1])
    assert list(doc)[-1] == "check"
    assert out.err.strip().splitlines()[-1].startswith("check correct")
    return doc
