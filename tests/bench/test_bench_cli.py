"""`bench/run.py` end to end on the CPU: it finds a tiny cell by name and
refuses to report, with no result line, because there is no accelerator;
and it refuses in a checkout that holds only the benchmark's files."""
import json
import os
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _checkout(tmp_path, with_program: bool):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    if with_program:
        (root / "src").symlink_to(os.path.join(ROOT, "src"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cfg = json.load(open(os.path.join(
        ROOT, "bench", "configs", "qwen1.5-0.5b-qat-w4a4.json")))
    cfg.update(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=4, intermediate_size=128, vocab_size=512)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (root / "bench" / "traffic" / "tiny.json").write_text(json.dumps(
        {"loop": "train", "batch": 2, "seq_len": 16, "setup_steps": 4,
         "trace_steps": 2}))
    spec["configs"].append(dict(spec["configs"][0], name="tiny",
                                file="bench/configs/tiny.json"))
    spec["workloads"].append({"name": "tiny", "config": "tiny",
                              "traffic": "tiny", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def _run(root, workload="tiny"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", workload, "--seed",
         str(2 ** 33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_an_accelerator(tmp_path):
    root = _checkout(tmp_path, with_program=True)
    p = _run(root)
    assert p.returncode != 0
    assert p.stdout.strip() == ""          # no result line, no metrics
    assert "no accelerator" in p.stderr
    assert not (root / ".jax_cache").exists() or not any(
        (root / ".jax_cache").iterdir())


def test_refuses_with_only_the_benchmark_files(tmp_path):
    root = _checkout(tmp_path, with_program=False)
    p = _run(root)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_refuses_an_unknown_workload(tmp_path):
    root = _checkout(tmp_path, with_program=True)
    p = _run(root, "no-such-cell")
    assert p.returncode != 0 and p.stdout.strip() == ""
