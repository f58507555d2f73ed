"""The harness finds a configuration, a traffic mix, a per-layer metric
and an architecture that a later change adds as files, by their names in
the manifest and the configuration, with no edit to any file already
there."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap

import archs
from bench_kit import BENCH

SRC = BENCH.parent / "src"
KIT = BENCH.parent / "tests" / "bench"


def load_run(root):
    spec = importlib.util.spec_from_file_location(
        "bench_run_copy", root / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def copy_bench(root):
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("out", ".jax_cache",
                                                  "__pycache__"))
    shutil.copy(BENCH.parent / "BENCHMARK.json", root)
    return root / "bench"


def python(script, cwd, timeout):
    """``script`` in a fresh CPU process from ``cwd``; its last line of
    output, read as JSON."""
    p = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)], cwd=cwd,
        capture_output=True, text=True, timeout=timeout,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_added_files_are_found_by_name(tmp_path):
    copy_bench(tmp_path)
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


def test_added_architecture_is_found_by_name(tmp_path):
    """A copy of Mixtral's files under a new ``model_type`` is reached by
    ``model``, ``reference``, ``check``, ``costs``, the readers and the
    control's study, and its tiny CPU cell is ``correct``."""
    b = copy_bench(tmp_path)
    for kind in ("arch", "ref"):
        shutil.copy(b / kind / "mixtral.py", b / kind / "mixtral_twin.py")
    (tmp_path / "cell").mkdir()
    out = python(f"""
        import json, pathlib, sys
        sys.path[:0] = [{str(b)!r}, {str(SRC)!r}, {str(KIT)!r}]
        import bench_kit, control, model, run
        found = bench_kit.tiny_cell(pathlib.Path("cell"), "mixtral_twin")
        c = json.loads(found["config_file"].read_text())
        (study,) = control.study(found, [2**33 + 1], 3.0,
                                 emit=lambda s: None)
        line = run.execute(found, bench_kit.run_args(seed=2**33 + 21),
                           [bench_kit.CpuDevice()], bench_kit.CPU_PEAK)
        loaded = sorted(m.__file__ for n, m in sys.modules.items()
                        if n.startswith(("bench_arch_", "bench_ref_")))
        mix = dict(c, model_type="mixtral")
        steps = [{{"pos": [3, 17], "emitted": 2, "rows": 2,
                   "sched/useful_rows": 12.0,
                   "sched/n_blocks_active": 6.0}}]
        trace = {{"window_s": 0.5, "kernel_s": dict.fromkeys(run.KERNELS,
                                                            1e-3)}}
        readers = {{
            t: [run.reader(m)({{"c": cc, "peak": bench_kit.CPU_PEAK,
                                "steps": steps, "trace": trace}})
                for m in ("model.mfu.serve", "expert_gemm_roofline.serve",
                          "moe_dispatch_roofline.serve")]
            for t, cc in (("twin", c), ("mixtral", mix))}}
        print(json.dumps({{
            "loaded": loaded, "study": study, "line": line,
            "same_config": repr(model.model_config(c)).replace(
                "mixtral_twin", "") == repr(model.model_config(mix)),
            "readers": readers}}))
        """, tmp_path, timeout=600)
    assert out["loaded"] == [str(b / "arch" / "mixtral_twin.py"),
                             str(b / "ref" / "mixtral_twin.py")]
    assert out["same_config"]
    assert out["readers"]["twin"] == out["readers"]["mixtral"]
    assert None not in out["readers"]["twin"]
    assert out["study"]["correct"] is True
    assert out["study"]["control_correct"] is False
    compared = {x["name"]: x["value"] for x in out["line"]["compared"]}
    assert out["line"]["correct"] is True, compared
    assert compared["checked_kv_rows"] >= 16


def test_an_architecture_reading_back_too_few_cache_leaves_fails(tmp_path):
    """A copy of Mixtral's files whose ``cache_leaves`` names the keys
    only: the run stops on the read-back rather than judge half the
    cache."""
    b = copy_bench(tmp_path)
    for kind in ("arch", "ref"):
        shutil.copy(b / kind / "mixtral.py", b / kind / "mixtral_k.py")
    f = b / "arch" / "mixtral_k.py"
    text = f.read_text()
    assert 'cache_leaves = ("k", "v")' in text
    f.write_text(text.replace('cache_leaves = ("k", "v")',
                              'cache_leaves = ("k",)'))
    (tmp_path / "cell").mkdir()
    out = python(f"""
        import json, pathlib, sys
        sys.path[:0] = [{str(b)!r}, {str(SRC)!r}, {str(KIT)!r}]
        import bench_kit, run
        found = bench_kit.tiny_cell(pathlib.Path("cell"), "mixtral_k")
        try:
            run.execute(found, bench_kit.run_args(seed=2**33 + 23),
                        [bench_kit.CpuDevice()], bench_kit.CPU_PEAK)
        except ValueError as e:
            print(json.dumps({{"raised": str(e)}}))
        else:
            print(json.dumps({{"raised": None}}))
        """, tmp_path, timeout=600)
    assert out["raised"] and "zip()" in out["raised"]


def test_an_unknown_architecture_fails_naming_the_known_ones(tmp_path):
    b = copy_bench(tmp_path)
    (tmp_path / "src").symlink_to(SRC)
    f = b / "configs" / "moonshot-16b-a3b-3l.json"
    f.write_text(json.dumps(dict(json.loads(f.read_text()),
                                 model_type="no_such_arch")))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "moonshot-chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout.strip() == ""
    for name in ("no_such_arch", "deepseek_v3.py", "mixtral.py"):
        assert name in p.stderr


def test_the_reference_imports_nothing_of_the_program():
    """``reference`` and every reference-side architecture file load
    where the program cannot be imported."""
    out = python(f"""
        import importlib.abc, json, sys

        class NoProgram(importlib.abc.MetaPathFinder):
            def find_spec(self, name, path=None, target=None):
                if name.split(".")[0] == "repro":
                    raise ImportError("the program is not importable")
        sys.meta_path.insert(0, NoProgram())
        sys.path.insert(0, {str(BENCH)!r})
        import archs, reference
        refs = archs.known("ref")
        for t in refs:
            archs.reference(t).forward
        print(json.dumps({{"refs": refs, "program": sorted(
            m for m in sys.modules if m.split(".")[0] == "repro")}}))
        """, BENCH.parent, timeout=120)
    assert out == {"refs": archs.known("ref"), "program": []}
    assert {"deepseek_v3", "mixtral"} <= set(out["refs"])


def test_a_checkout_without_the_program_fails_with_no_result(tmp_path):
    copy_bench(tmp_path)
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
