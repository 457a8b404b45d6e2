"""The harness's contract, on the CPU: it refuses to run without a TPU, the
manifest keeps to its rules, and a configuration, a traffic mix with its
driver and a per-layer metric are found from new files and manifest
entries alone."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

import harness
import run

ROOT = harness.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def manifest():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def test_refuses_a_cpu_and_prints_no_result():
    cell = manifest()["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", cell, "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "cpu" in p.stderr


def test_refuses_without_the_program(tmp_path):
    """A directory with only the manifest and the benchmark's own files."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cell = manifest()["workloads"][0]["name"]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload", cell, "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_manifest_keeps_to_its_rules():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert m["paths"] == ["bench"] and m["command"][1] == "bench/run.py"
    assert 1 <= m["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer") for x in m[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(x["name"] for x in m["configs"])) == len(m["configs"])
    assert len(set(x["name"] for x in m["workloads"])) == len(m["workloads"])
    metrics = m["end_to_end"] + m["per_layer"]
    assert len(set(x["name"] for x in metrics)) == len(metrics)
    for x in metrics:
        assert UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= x["bound"] <= 0.25 for x in m["end_to_end"])
    cells = {w["name"]: w for w in m["workloads"]}
    reg = harness.Registry()
    for w in m["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200 and NAME.match(w["traffic"])
        traffic = reg.traffic(w["traffic"])
        reg.driver(traffic)
        reg.model(reg.config(w["config"]))
        assert harness.limits(w["name"])
        assert len(reg.end_to_end(w["name"])) >= 2
        assert reg.per_layer(w["name"])
    for x in m["per_layer"]:
        assert x["moves"] in e2e and x["moves"] != "setup_s"
        reg.reader(x["name"])
        for cell in x["workloads"]:
            assert cell in cells and cell in e2e[x["moves"]].get("workloads", [cell])
        assert "\n" not in x["layer"] and len(x["layer"]) <= 200
    for c in m["configs"]:
        assert c["file"].startswith("bench/configs/") and NAME.match(c["name"])
        assert all(NAME.match(k) for k in c["reduced"])


TOY_DRIVER = '''
import time

import numpy as np


def unit(ctx, u):
    with ctx.span("bench.toy"):
        time.sleep(0.01)
    return u


def run(ctx):
    time.sleep(0.01)
    kept = ctx.closed_loop(lambda u: unit(ctx, u), warmup=1, check=1, check_within=2,
                           rng=np.random.default_rng(ctx.seed), counter="units")
    n = int(ctx.counters["units"])
    ctx.facts = {"units": n, "size": ctx.config["size"] * ctx.traffic["factor"]}
    return {"attempted": n, "failed": 0, "end_to_end": {"toy_s": ctx.window_s / n},
            "compared": {"toy_gap": {"value": float(any(k != v for k, v in kept.items())),
                                     "limit": 0.0}}}
'''
TOY_METRIC = '''
def read(ctx, summary, res):
    return 100.0 * summary.busy_s / summary.window_s + 0 * ctx.facts["size"]
'''


@pytest.fixture
def toy_root(tmp_path, monkeypatch):
    """A checkout with one more configuration, traffic mix, driver, model,
    metric and cell: new files and new manifest entries, nothing edited."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = tmp_path / "bench"
    m = manifest()
    m["configs"].append({"name": "toy", "source": "https://example.org/toy",
                         "file": "bench/configs/toy.json", "reduced": [], "why": "a toy"})
    m["workloads"].append({"name": "toy.cell", "config": "toy", "traffic": "toymix",
                           "chips": 1, "why": "a toy cell"})
    m["end_to_end"].append({"name": "toy_s", "unit": "s", "better": "lower", "bound": 0.05,
                            "source": "host_clock", "workloads": ["toy.cell"]})
    m["per_layer"].append({"name": "toy_metric", "unit": "%", "better": "higher",
                           "source": "device_trace", "layer": "toy", "moves": "toy_s",
                           "workloads": ["toy.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    (b / "configs" / "toy.json").write_text(json.dumps({"family": "toy", "size": 3}))
    (b / "models" / "toy.py").write_text('"""A toy model."""\n')
    (b / "traffic" / "toymix.json").write_text(json.dumps({"driver": "toy", "factor": 2}))
    (b / "drivers" / "toy.py").write_text(TOY_DRIVER)
    (b / "metrics" / "toy_metric.py").write_text(TOY_METRIC)
    (b / "limits" / "toy.cell.json").write_text("{}")
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(harness, "BENCH", str(b))
    monkeypatch.setattr(run, "find_device", lambda chips: {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1})
    monkeypatch.setattr(run, "configure_cache", lambda: None)
    return tmp_path


def test_a_new_cell_is_found_from_new_files(toy_root, capsys):
    assert run.main(["--workload", "toy.cell", "--seed", str(2**40), "--seconds", "0.2",
                     "--trace", "0"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["correct"] is True and out["attempted"] > 0
    assert set(out["metrics"]) == {"toy_s", "setup_s"}
    assert out["device"]["kind"] == "TPU v5 lite"
    assert list(out)[-1] == "compared"


def test_a_new_metric_is_read_in_the_traced_run(toy_root, capsys, monkeypatch):
    from jax.profiler import ProfileData

    import test_bench_trace
    import trace

    monkeypatch.setattr(trace, "find_xplane", lambda d: d)
    monkeypatch.setattr(trace, "load", lambda p: trace.planes_of(
        ProfileData.from_text_proto(test_bench_trace.XSPACE)))
    assert run.main(["--workload", "toy.cell", "--seed", "7", "--seconds", "0.2",
                     "--trace", "1"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["metrics"] == {"toy_metric": {"value": pytest.approx(40.0), "unit": "%"}}
    assert out["device"]["busy_s"] > 0 and out["device"]["window_s"] > 0
    assert out["breakdown"]["device_ops"]


def test_a_metric_that_finds_nothing_is_left_out_and_named(toy_root, capsys, monkeypatch):
    from jax.profiler import ProfileData

    import test_bench_trace
    import trace

    m = json.loads((toy_root / "BENCHMARK.json").read_text())
    m["per_layer"].append({"name": "toy_silent", "unit": "%", "better": "higher",
                           "source": "device_trace", "layer": "toy", "moves": "toy_s",
                           "workloads": ["toy.cell"]})
    (toy_root / "BENCHMARK.json").write_text(json.dumps(m))
    (toy_root / "bench" / "metrics" / "toy_silent.py").write_text(
        "def read(ctx, summary, res):\n    return None\n")
    monkeypatch.setattr(trace, "find_xplane", lambda d: d)
    monkeypatch.setattr(trace, "load", lambda p: trace.planes_of(
        ProfileData.from_text_proto(test_bench_trace.XSPACE)))
    assert run.main(["--workload", "toy.cell", "--seed", "7", "--seconds", "0.2",
                     "--trace", "1"]) == 0
    cap = capsys.readouterr()
    assert set(json.loads(cap.out.strip().splitlines()[-1])["metrics"]) == {"toy_metric"}
    assert "toy_silent found nothing to read in toy.cell" in cap.err
