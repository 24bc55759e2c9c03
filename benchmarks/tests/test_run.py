"""The command end to end at a tiny size on the CPU: the rehearsal path, the
manifest's contract, the faults ``correct`` has to catch, and that a new
cell, configuration and metric are files and entries, not edits."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)


def _run_module():
    spec = importlib.util.spec_from_file_location("bench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _rehearse(capsys, *extra):
    run = _run_module()
    assert run.main(["--seed", str(2**31 + 77), "--seconds", "2", "--rehearse", *extra]) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


CELLS = [c["name"] for c in _manifest()["workloads"]]


def _config_of(cell):
    m = _manifest()
    name = next(c["config"] for c in m["workloads"] if c["name"] == cell)
    with open(os.path.join(ROOT, next(c["file"] for c in m["configs"] if c["name"] == name))) as f:
        return json.load(f)


def _traffic_of(cell):
    traffic = next(c["traffic"] for c in _manifest()["workloads"] if c["name"] == cell)
    with open(os.path.join(BENCH, "workloads", traffic + ".json")) as f:
        return json.load(f)


def _cells_of(runner):
    return [c for c in CELLS if _config_of(c)["runner"] == runner]


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_drives_each_cell_and_fills_no_device_metric(cell, capsys):
    out = _rehearse(capsys, "--workload", cell, "--control")
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "checks"
    assert out["rehearsal"] is True and out["device"]["platform"] == "cpu"
    assert out["correct"] is True and out["attempted"] > 0 and out["failed"] == 0
    sources = {m["name"]: m["source"] for g in ("end_to_end", "per_layer") for m in _manifest()[g]}
    assert all(sources[name] == "program_counter" for name in out["metrics"])
    assert all(c["value"] <= c["limit"] for c in out["checks"] if c["limit"] is not None)
    assert sum(c["limit"] is not None for c in out["checks"]) >= 3
    # every control (the reference one precision down, in the program's place) and every fault planted in the
    # reference goes through the same comparison and has to come out NOT correct
    cfg = _config_of(cell)
    assert set(out["controls"]) == set(cfg["controls"]) | {"fault:" + f for f in cfg.get("faults", ())}
    assert cfg["control"] in out["controls"]
    for name, theirs in out["controls"].items():
        assert theirs["correct_if_control"] is False, name
        assert any(c["limit"] is not None and c["value"] > c["limit"] for c in theirs["checks"]), name


@pytest.mark.parametrize("sampled, number", [(False, "served_logit_gap"), (True, "sampled_logit_gap")])
def test_a_token_altered_where_it_is_produced_is_not_correct(sampled, number, capsys, monkeypatch):
    from benchmarks.runners import serve
    real = serve.Runner._submit

    def altered(self, prompt, max_new, temperature):
        handle = real(self, prompt, max_new, temperature)

        def stream():
            for i, tok in enumerate(handle):
                yield (int(tok) + 1) % 211 if i >= 1 and (temperature > 0) == sampled else tok
        return stream()

    monkeypatch.setattr(serve.Runner, "_submit", altered)
    cell = next(c for c in _cells_of("serve") if not sampled or _traffic_of(c).get("sampled_share"))
    out = _rehearse(capsys, "--workload", cell)
    assert out["correct"] is False
    over = [c["name"] for c in out["checks"] if c["limit"] is not None and c["value"] > c["limit"]]
    assert number in over and (sampled or "served_logit_gap_sq_mean" in over)


def test_half_of_the_batch_left_out_is_not_correct(capsys, monkeypatch):
    from benchmarks.runners import train
    monkeypatch.setattr(train.Runner, "fault", "half_batch")     # the program sees the first half of the rows twice
    out = _rehearse(capsys, "--workload", _cells_of("train")[0])
    assert out["correct"] is False and out["failed"] == 0
    over = [c["name"] for c in out["checks"] if c["limit"] is not None and c["value"] > c["limit"]]
    assert "first_gradient_gap" in over


def test_a_step_that_returns_its_state_unchanged_is_not_correct(capsys, monkeypatch):
    from bigdl_tpu.optim import methods
    real = methods.SGD.apply_update

    def unchanged(self, grads, opt_state, params, lr):
        return params, real(self, grads, opt_state, params, lr)[1]

    monkeypatch.setattr(methods.SGD, "apply_update", unchanged)
    out = _rehearse(capsys, "--workload", _cells_of("train")[0])
    assert out["correct"] is False
    change = next(c for c in out["checks"] if c["name"] == "change_gap")
    assert change["value"] == pytest.approx(1.0) and change["value"] > change["limit"]


def test_a_request_that_never_answers_is_failed_not_dropped(capsys, monkeypatch):
    from benchmarks.runners import serve
    real = serve.Runner._submit
    seen = []

    def refusing(self, prompt, max_new, temperature):
        seen.append(1)
        if len(seen) % 3 == 0:
            raise RuntimeError("queue full")
        return real(self, prompt, max_new, temperature)

    monkeypatch.setattr(serve.Runner, "_submit", refusing)
    out = _rehearse(capsys, "--workload", _cells_of("serve")[0])
    assert out["failed"] > 0 and out["correct"] is False


def test_without_a_tpu_a_measurement_exits_and_prints_no_result():
    proc = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELLS[0],
                           "--seed", "1", "--seconds", "1", "--trace", "0"],
                          env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "TPU only" in proc.stderr
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())


# ------------------------------------------------------------ the manifest --
NAME = r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$"


def test_manifest_keeps_the_contract():
    import re
    m = _manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert m["paths"] == ["benchmarks"] and 1 <= m["run_seconds"] <= 51
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for x in m["end_to_end"]:
        assert set(x) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= x["bound"] <= 0.1 and x["source"] in ("host_clock", "device_trace")
    cells = {c["name"]: c for c in m["workloads"]}
    configs = {c["name"]: c for c in m["configs"]}
    pairs = set()
    for c in m["workloads"]:
        assert re.match(NAME, c["name"]) and c["chips"] in (1, 4) and len(c["why"]) <= 200
        assert c["config"] in configs and (c["config"], c["traffic"]) not in pairs
        pairs.add((c["config"], c["traffic"]))
        assert os.path.exists(os.path.join(BENCH, "workloads", c["traffic"] + ".json"))
    for c in m["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"])) and len(c["why"]) <= 200
        assert any(w["config"] == c["name"] for w in m["workloads"])
    run = _run_module()
    for x in m["per_layer"]:
        assert set(x) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert re.match(NAME, x["name"]) and x["moves"] in e2e and x["better"] in ("lower", "higher")
        assert x["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert os.path.exists(os.path.join(BENCH, "metrics", x["name"] + ".py"))
        for w in x.get("workloads", ()):
            assert w in cells
    for cell in m["workloads"]:
        reported = {x["name"] for x in run.metrics_for(m, cell, "end_to_end")}
        assert "setup_s" in reported and len(reported) >= 2
        layer = run.metrics_for(m, cell, "per_layer")
        assert layer and all(x["moves"] in reported for x in layer)
    for name in e2e:
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))


def test_the_harness_names_no_cell_configuration_or_metric():
    m = _manifest()
    names = ([c["name"] for c in m["workloads"]] + [c["name"] for c in m["configs"]]
             + [x["name"] for x in m["end_to_end"] + m["per_layer"]])
    files = [os.path.join(BENCH, "run.py")] + [
        os.path.join(BENCH, "harness", f) for f in os.listdir(os.path.join(BENCH, "harness")) if f.endswith(".py")]
    for path in files:
        with open(path) as f:
            text = f.read()
        for name in names:
            assert name not in text, f"{os.path.basename(path)} names {name!r}"


# --------------------------------------- a new cell is files and entries --
def test_a_new_cell_configuration_and_metric_are_files_and_entries(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks", ignore=shutil.ignore_patterns("__pycache__", "tests"))
    m = _manifest()
    base_cfg = json.load(open(os.path.join(ROOT, m["configs"][0]["file"])))
    base_cfg["rehearse"]["engine_kwargs"]["max_slots"] = 3          # y: another configuration
    (root / "benchmarks" / "configs" / "y.json").write_text(json.dumps(base_cfg))
    (root / "benchmarks" / "workloads" / "x.json").write_text(json.dumps({   # x: another traffic mix
        "loop": "open", "arrivals": "poisson", "rate_per_s": 5.0, "lead_in_s": 0.2,
        "prompt_tokens": {"dist": "uniform", "min": 4, "max": 20}, "output_tokens": {"dist": "uniform", "min": 4, "max": 6},
        "sampled_share": 0.0}))
    (root / "benchmarks" / "metrics" / "z.py").write_text(              # z: another per-layer metric
        "def read(ctx):\n    return float(len(ctx.records))\n")
    m["configs"].append({"name": "y", "source": "throw-away", "file": "benchmarks/configs/y.json",
                         "reduced": [], "why": "throw-away"})
    m["workloads"].append({"name": "x-on-y", "config": "y", "traffic": "x", "chips": 1, "why": "throw-away"})
    for e in m["end_to_end"]:
        if "workloads" in e and e["name"] == "tpot_p95_ms":
            e["workloads"].append("x-on-y")
    m["per_layer"].append({"name": "z", "unit": "count", "better": "higher", "source": "program_counter",
                           "layer": "throw-away", "moves": "tpot_p95_ms", "workloads": ["x-on-y"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    proc = subprocess.run([sys.executable, str(root / "benchmarks" / "run.py"), "--workload", "x-on-y", "--seed", "5",
                           "--seconds", "2", "--trace", "1", "--rehearse"],
                          env=env, capture_output=True, text=True, timeout=600, cwd=root)
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["attempted"] >= 8
    assert out["metrics"]["z"]["value"] >= out["attempted"]


def test_in_a_directory_with_only_the_benchmark_it_exits_and_prints_no_result(tmp_path):
    root = tmp_path / "bare"
    shutil.copytree(BENCH, root / "benchmarks", ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, str(root / "benchmarks" / "run.py"), "--workload", CELLS[0], "--seed", "1",
                           "--seconds", "1", "--trace", "0", "--rehearse"],
                          env=env, capture_output=True, text=True, timeout=300, cwd=root)
    assert proc.returncode != 0
    assert not any(line.startswith("{") for line in proc.stdout.splitlines())
