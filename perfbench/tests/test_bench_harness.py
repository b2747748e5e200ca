"""The harness's own pieces on the CPU: discovery by name, the result
line's schema, and the refusal to run without a chip."""
import json
import os
import re
import shutil
import subprocess
import sys
import types

import run as harness

ROOT = harness.ROOT


def _bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def test_every_cell_resolves_by_name():
    bench = _bench()
    for cell in bench["workloads"]:
        found = harness.find_cell(bench, cell["name"])
        assert found["config"]["driver"]
        assert os.path.exists(os.path.join(
            harness.BENCH, "drivers", found["config"]["driver"] + ".py"))
        for m in found["per_layer"]:
            assert os.path.exists(os.path.join(
                harness.BENCH, "metrics", m["name"] + ".py")), m["name"]
        names = {m["name"] for m in found["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert found["per_layer"]


NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
ENTRY_KEYS = {
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def test_benchmark_file_keeps_its_format():
    """Keys, names, units, one-line texts, and the references between
    entries, as the harness and its checker read them."""
    bench = _bench()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    for group, keys in ENTRY_KEYS.items():
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names)), group
        for e in bench[group]:
            assert set(e) - {"workloads"} == keys, (group, e["name"])
            assert NAME.fullmatch(e["name"]), e["name"]
            for text in ("why", "layer", "source"):
                if text in e and group in ("configs", "workloads",
                                           "per_layer"):
                    assert 1 <= len(e[text]) <= 200
                    assert "\n" not in e[text] and "\t" not in e[text]
            if "unit" in e:
                assert UNIT.fullmatch(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    configs = {c["name"] for c in bench["configs"]}
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        assert w["config"] in configs and NAME.fullmatch(w["traffic"])
        assert w["chips"] in (1, 4)
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells


def test_a_new_cell_config_traffic_and_metric_need_no_edit(tmp_path):
    """Files and entries only: a copied configuration, a new traffic mix
    and a new metric reader are found by name."""
    bench = _bench()
    root = tmp_path
    shutil.copytree(harness.BENCH, root / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    cfg = bench["configs"][0]
    new_cfg = dict(cfg, name="copy-cfg",
                   file="perfbench/configs/copy-cfg.json")
    shutil.copy(os.path.join(ROOT, cfg["file"]),
                root / "perfbench" / "configs" / "copy-cfg.json")
    cell = bench["workloads"][0]
    traffic = harness.load_json(os.path.join(
        harness.BENCH, "traffic", cell["traffic"] + ".json"))
    traffic["eval_every"] = 2
    (root / "perfbench" / "traffic" / "new-mix.json").write_text(
        json.dumps(traffic))
    (root / "perfbench" / "metrics" / "new_metric.py").write_text(
        "def read(ctx):\n    return 2.0 * ctx['rounds']\n")
    moves = [m for m in bench["end_to_end"]
             if cell["name"] in m.get("workloads", [cell["name"]])
             and m["name"] != "setup_s"][0]["name"]
    bench["configs"].append(new_cfg)
    bench["workloads"].append(dict(cell, name="copy.new", config="copy-cfg",
                                   traffic="new-mix"))
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append("copy.new")
    bench["per_layer"].append({"name": "new_metric", "unit": "1",
                               "better": "lower", "source": "host_clock",
                               "layer": "entry / driver", "moves": moves,
                               "workloads": ["copy.new"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    found = harness.find_cell(harness.load_json(root / "BENCHMARK.json"),
                              "copy.new", root=str(root))
    assert found["traffic"]["eval_every"] == 2
    assert found["config"] == harness.load_json(os.path.join(ROOT,
                                                             cfg["file"]))
    assert [m["name"] for m in found["per_layer"]] == ["new_metric"]
    got = harness.read_layer_metrics(found["per_layer"], {"rounds": 3},
                                     bench=str(root / "perfbench"))
    assert got == {"new_metric": {"value": 6.0, "unit": "1"}}


class _FakeDevice:
    platform, device_kind = "tpu", "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 123}


def test_result_line_schema():
    """The last line's keys, in order, with the compared numbers last."""
    bench = _bench()
    found = harness.find_cell(bench, bench["workloads"][0]["name"])

    def run(config, traffic, *, seed, seconds, window, devices):
        window.open()
        window.close()
        e2e = {m["name"]: 1.5 for m in found["end_to_end"]
               if m["name"] != "setup_s"}
        return {"attempted": 8, "failed": 0, "e2e": e2e,
                "ctx": {},
                "checks": [("loss_gap", 0.1, 0.2), ("update_gap", 0.3, 0.2)]}

    line = harness.run_cell(found, seed=2**31 + 9, seconds=1.0, trace=False,
                            devices=[_FakeDevice()],
                            driver=types.SimpleNamespace(run=run))
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    assert line["correct"] is False          # update_gap over its limit
    assert set(line["metrics"]) == {m["name"] for m in found["end_to_end"]}
    for v in line["metrics"].values():
        assert set(v) == {"value", "unit"}
    assert line["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1, "memory_peak_bytes": 123}
    assert line["checks"]["loss_gap"] == {"value": 0.1, "limit": 0.2}
    json.dumps(line)


def _run_bench(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    workload = _bench()["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", workload,
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_no_chip_no_result():
    proc = _run_bench(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_only_the_benchmark_files_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_bench(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
