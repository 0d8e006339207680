"""The examples ladder doubles as integration tests (reference practice,
SURVEY.md §4) — run each example script end-to-end."""

import os
import subprocess
import sys

import pytest

EXAMPLES = os.path.join(os.path.dirname(__file__), "..", "examples")


def run_example(name, *args, timeout=240):
    env = dict(os.environ)
    # examples must run on the local CPU backend to be fast and
    # deterministic (and a test must never take the chip)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = (
        os.path.abspath(os.path.join(EXAMPLES, ""))
        + os.pathsep
        + os.path.abspath(os.path.join(EXAMPLES, ".."))
        + os.pathsep
        + env.get("PYTHONPATH", "")
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, name), *args],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=EXAMPLES,
    )
    assert proc.returncode == 0, f"{name} failed:\n{proc.stdout}\n{proc.stderr}"
    return proc.stdout


def test_example_1_sequential():
    out = run_example("example_1_local_sequential.py", "--n_iterations", "2")
    assert "best found configuration" in out


def test_example_2_threads():
    out = run_example(
        "example_2_local_parallel_threads.py", "--n_workers", "3",
        "--n_iterations", "2",
    )
    assert "best:" in out


@pytest.mark.slow

def test_example_3_processes():
    out = run_example(
        "example_3_local_parallel_processes.py", "--n_workers", "2",
        "--n_iterations", "2",
    )
    assert "best:" in out


@pytest.mark.slow

def test_example_5_mlp_worker():
    out = run_example(
        "example_5_mlp_worker.py", "--n_workers", "1", "--n_iterations", "1",
        "--min_budget", "5", "--max_budget", "15", timeout=420,
    )
    assert "val loss at max budget" in out


@pytest.mark.slow

def test_example_6_analysis_warmstart(tmp_path):
    out = run_example(
        "example_6_analysis_warmstart.py", "--out_dir", str(tmp_path), "--plot",
    )
    assert "phase 3 final incumbent loss" in out
    assert (tmp_path / "losses_over_time.png").exists()


@pytest.mark.slow

def test_example_7_tpu_batched():
    out = run_example(
        "example_7_tpu_batched.py", "--n_iterations", "2",
        "--min_budget", "5", "--max_budget", "45",
    )
    assert "configs/s" in out


def test_example_8_large_sweep():
    out = run_example(
        "example_8_large_sweep.py", "--n_iterations", "4", "--max_budget", "9"
    )
    assert "incumbent loss" in out
    assert "fused whole-sweep" in out


def test_example_8_large_sweep_chunked_checkpoint(tmp_path):
    out = run_example(
        "example_8_large_sweep.py", "--n_iterations", "4", "--max_budget", "9",
        "--chunk_brackets", "2", "--checkpoint", str(tmp_path / "sweep.pkl"),
    )
    assert "incumbent loss" in out
    assert "2-bracket chunks" in out
    assert (tmp_path / "sweep.pkl").exists()


def test_example_8_large_sweep_per_bracket():
    out = run_example(
        "example_8_large_sweep.py", "--n_iterations", "4", "--max_budget", "9",
        "--no-fused",
    )
    assert "incumbent loss" in out
    assert "per-bracket batched" in out


def test_example_9_multihost_batched_workers():
    out = run_example(
        "example_9_multihost_batched_workers.py",
        "--n_iterations", "3", "--max_budget", "9",
    )
    assert "batched workers" in out
    assert "incumbent loss" in out


@pytest.mark.slow
def test_example_10_multihost_fused_spmd():
    # self-launch demo: 2 jax.distributed ranks, 4-device pod, fused sweep,
    # asserts cross-rank run-record agreement internally
    out = run_example("example_10_multihost_fused_spmd.py", timeout=600)
    assert "SPMD OK" in out


def test_example_12_long_context_ring():
    out = run_example(
        "example_12_long_context_ring.py", "--seq_per_device", "32",
        "--head_dim", "16", "--striped",
    )
    assert "never" in out and "grads finite: OK" in out
    assert "prefix parity vs dense" in out


@pytest.mark.slow
def test_example_11_transformer_fused():
    out = run_example(
        "example_11_transformer_fused.py", "--tiny",
        "--n_iterations", "2", "--min_budget", "9", "--max_budget", "81",
    )
    assert "configs/s" in out
    assert "copied-half val accuracy" in out
