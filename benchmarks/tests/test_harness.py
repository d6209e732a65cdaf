"""The harness refuses a platform that is not a TPU outside its
rehearsal, and BENCHMARK.json names only files that exist."""
import json
import os
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def test_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "chat-decode", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert out.returncode != 0
    assert "Refusing to run" in out.stderr
    assert not any(ln.startswith('{"correct"') for ln in out.stdout.splitlines())


def test_benchmark_json_is_data_driven():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    names = {w["name"] for w in b["workloads"]}
    for c in b["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in b["workloads"]:
        cell = json.load(open(os.path.join(BENCH, "workloads", w["name"] + ".json")))
        assert os.path.exists(os.path.join(BENCH, "drivers", cell["driver"] + ".py"))
        assert os.path.exists(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    e2e = {m["name"] for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert os.path.exists(os.path.join(BENCH, "layer_metrics", m["name"] + ".py"))
        assert m["moves"] in e2e
        assert set(m.get("workloads", names)) <= names
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(1, len(names) // 4)
