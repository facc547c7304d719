"""The harness on the CPU: pieces found by name, the operation count,
the refusal to run without a TPU, and the program's default path."""
import json
import os
import re
import subprocess
import sys

from chipbench import flops
from chipbench.harness import BENCH_DIR, REPO, Bench
from chipbench.tests import tiny


def _env(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO), str(REPO / "src"), env.get("PYTHONPATH", "")])
    return env


def _result(proc):
    lines = proc.stdout.strip().splitlines()
    return proc.returncode, json.loads(lines[-1]) if lines else None, \
        proc.stderr


def _args(cell, seed, seconds):
    return ["--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", "0"]


def run_tiny(tmp_path, cell, fault="none", seed=4000000007, seconds=0.5,
             devices=1):
    """One run of ``cell`` at test sizes in a fresh process on
    ``devices`` CPU devices, with ``fault`` planted; returns (exit code,
    result line or None, stderr)."""
    dest = tmp_path / f"bench-{cell}-{fault}"
    env = _env(tmp_path)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    return _result(subprocess.run(
        [sys.executable, "-m", "chipbench.tests.tiny", str(dest), fault]
        + _args(cell, seed, seconds), cwd=REPO, env=env,
        capture_output=True, text=True, timeout=600))


def run_bench(tmp_path, dest, cell, seed=6000000013, seconds=0.5,
              devices=1):
    """One run of ``cell`` from the benchmark files in ``dest``, on
    ``devices`` CPU devices, in a fresh process."""
    env = _env(tmp_path)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    code = ("import sys; from chipbench.harness import Bench, run; "
            "from chipbench import program; program.import_program(); "
            f"b = Bench({str(dest)!r}, {str(dest / 'BENCHMARK.json')!r}); "
            "sys.exit(run(sys.argv[1:], bench=b, require_tpu=False))")
    return _result(subprocess.run(
        [sys.executable, "-c", code] + _args(cell, seed, seconds), cwd=REPO,
        env=env, capture_output=True, text=True, timeout=600))


def test_refuses_to_run_without_a_tpu(tmp_path):
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "qwen2-7b-dpsgd-s1k", "--seed", "5000000011", "--seconds", "1",
         "--trace", "0"],
        cwd=REPO, env=_env(tmp_path), capture_output=True, text=True,
        timeout=300)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_every_cell_names_files_that_exist():
    bench = Bench()
    for w in bench.spec["workloads"]:
        cell = bench.workload(w["name"])
        cfg = bench.config(cell["config"])
        bench.job(bench.mix(cell["traffic"])["job"])
        bench.family(cfg["family"])
        for kind in ("end_to_end", "per_layer"):
            assert bench.metrics(w["name"], kind)
    for c in bench.spec["configs"]:
        assert (REPO / c["file"]).is_file()


def test_the_default_path_is_driven():
    """No pinned stats method or backend, and no ``Engine`` sugar: the
    cells measure what ``PexSpec()`` chooses."""
    banned = re.compile(r"use_pallas|seg_method|method\s*=|value_and_norms|"
                        r"value_grads_and_norms|clipped_step|"
                        r"gradient_noise_scale")
    for path in BENCH_DIR.rglob("*.py"):
        if "tests" in path.parts:
            continue
        code = re.sub(r'"""[\s\S]*?"""', "", path.read_text())
        code = re.sub(r"#.*", "", code)
        assert not banned.search(code), path


def test_operations_per_token_from_the_published_widths():
    cfg = Bench().config("qwen2-7b-2l")
    d, f, heads, kv, hd, layers, vocab = 3584, 18944, 28, 4, 128, 2, 19008
    matrices = layers * (d * heads * hd + 2 * d * kv * hd + heads * hd * d
                         + 3 * d * f) + d * vocab
    assert matrices == 534_216_704
    attention = layers * 2 * 2 * 512.5 * hd * heads   # causal half, S 1024
    assert flops.per_token(cfg, 1024, weight_grads=True) == \
        6 * matrices + 3 * attention
    assert flops.per_token(cfg, 1024, weight_grads=False) == \
        4 * matrices + 3 * attention


def test_pieces_are_found_by_name_alone(tmp_path):
    """A configuration, a traffic mix, a cell, a job kind and a metric
    are added as new files and new index entries; no file the harness
    already had changes."""
    dest = tmp_path / "bench"
    tiny.make(dest)
    before = {p: p.read_bytes() for p in dest.rglob("*") if p.is_file()}
    cfg = json.loads((dest / "configs" / "qwen2-7b-2l.json").read_text())
    cfg.update(num_hidden_layers=1)
    cfg["program"]["n_layers"] = 1
    (dest / "configs" / "qwen2-7b-1l.json").write_text(json.dumps(cfg))
    mix = json.loads((dest / "mixes" / "plain-b8-s1024.json").read_text())
    mix.update(job="finetune", batch=2, seq=64)
    (dest / "mixes" / "finetune-b2-s64.json").write_text(json.dumps(mix))
    (dest / "jobs" / "finetune.py").write_text(
        "from chipbench.training import TrainJob\n\n\n"
        "class Job(TrainJob):\n    pass\n")
    (dest / "metrics" / "steps_per_s.py").write_text(
        "def read(run):\n    return run['steps'] / run['window_s']\n")
    (dest / "workloads" / "qwen2-1l-finetune.json").write_text(json.dumps(
        {"config": "qwen2-7b-1l", "traffic": "finetune-b2-s64", "chips": 1,
         "limits": tiny.LIMITS["plain"], "why": "test"}))
    index = json.loads((dest / "BENCHMARK.json").read_text())
    index["configs"].append({"name": "qwen2-7b-1l", "source": "test",
                             "file": "x", "reduced": [], "why": "test"})
    index["workloads"].append({"name": "qwen2-1l-finetune",
                               "config": "qwen2-7b-1l",
                               "traffic": "finetune-b2-s64", "chips": 1,
                               "why": "test"})
    index["end_to_end"].append({"name": "steps_per_s", "unit": "1/s",
                                "better": "higher", "bound": 0.05,
                                "source": "host_clock",
                                "workloads": ["qwen2-1l-finetune"]})
    (dest / "BENCHMARK.json").write_text(json.dumps(index))
    for path, data in before.items():
        if path.name != "BENCHMARK.json":
            assert path.read_bytes() == data, path

    code, result, err = run_bench(tmp_path, dest, "qwen2-1l-finetune")
    assert code == 0, err[-3000:]
    assert result["correct"], result
    assert result["metrics"]["steps_per_s"]["value"] > 0
    assert set(result["metrics"]) == {"tokens_per_s", "peak_hbm_gib",
                                      "setup_s", "steps_per_s"}
