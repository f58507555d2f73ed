"""The harness finds a configuration, a traffic mix and a per-layer metric
that a later change adds as files, by their names in the manifest, with
no edit to any file already there."""
import importlib.util
import json
import shutil
import subprocess
import sys

from bench_kit import BENCH


def load_run(root):
    spec = importlib.util.spec_from_file_location(
        "bench_run_copy", root / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_added_files_are_found_by_name(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", ".jax_cache",
                                                  "__pycache__"))
    man = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "moonshot-16b-a3b-3l.json").read_text())
    cfg["name"] = "moonshot-copy"
    (b / "configs" / "moonshot-copy.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "chat.json").read_text())
    mix["arrivals"]["rate_per_s"] = 1.25
    (b / "traffic" / "slow_chat.json").write_text(json.dumps(mix))
    (b / "metrics" / "new.thing.py").write_text(
        "def read(run):\n    return run['seen'] * 2\n")
    man["configs"].append({"name": "moonshot-copy", "source": "x",
                           "file": "bench/configs/moonshot-copy.json",
                           "reduced": [], "why": "x"})
    man["workloads"].append({"name": "moonshot-slowchat",
                             "config": "moonshot-copy",
                             "traffic": "slow_chat", "chips": 1, "why": "x"})
    man["per_layer"].append({"name": "new.thing", "unit": "count",
                             "better": "lower", "source": "host_clock",
                             "layer": "x", "moves": "tpot_p50_ms",
                             "workloads": ["moonshot-slowchat"]})
    for m in man["end_to_end"]:
        if m["name"] == "tpot_p50_ms":
            m["workloads"].append("moonshot-slowchat")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(man))

    run = load_run(tmp_path)
    found = run.cell_of(run.manifest(), "moonshot-slowchat")
    assert found["config_file"] == b / "configs" / "moonshot-copy.json"
    assert found["traffic_file"] == b / "traffic" / "slow_chat.json"
    assert [m["name"] for m in found["end_to_end"]] == ["setup_s",
                                                         "tpot_p50_ms"]
    assert [m["name"] for m in found["per_layer"]] == ["new.thing"]
    assert run.reader("new.thing")({"seen": 21}) == 42
    # the cells already there are unchanged
    old = run.cell_of(run.manifest(), "moonshot-chat")
    assert "new.thing" not in [m["name"] for m in old["per_layer"]]


def test_every_manifest_name_has_its_file():
    man = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    for c in man["configs"]:
        assert (BENCH.parent / c["file"]).is_file()
    for w in man["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in man["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_a_checkout_without_the_program_fails_with_no_result(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("out", ".jax_cache",
                                                  "__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "moonshot-chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_a_run_without_a_tpu_fails_with_no_result():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "moonshot-chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=BENCH.parent, capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(BENCH.parent / "bench" / "out")})
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr
