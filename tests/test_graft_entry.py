"""The driver-facing entry points must be hermetic.

The multichip dry run once died when the ambient default platform was an
unhealthy TPU, because the mesh body ran in-process. These tests assert the
wrapper re-execs in a CPU-forced child so a broken ambient platform can
never fail the virtual-mesh gate.
"""

import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as graft  # noqa: E402


@pytest.mark.slow
def test_dryrun_multichip_survives_broken_ambient_platform(monkeypatch):
    """dryrun_multichip(8) must pass even when JAX_PLATFORMS in the calling
    process points at a platform that does not exist (simulating an
    unusable ambient TPU)."""
    monkeypatch.setenv("JAX_PLATFORMS", "no_such_tpu_platform")
    monkeypatch.setenv(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=1"
    )
    graft.dryrun_multichip(8)  # raises RuntimeError on child failure


def test_dryrun_child_env_is_cpu_pinned(monkeypatch):
    """The wrapper must pin JAX_PLATFORMS=cpu and the device-count flag in
    the child env regardless of what the parent env says."""
    captured = {}

    def fake_run(cmd, env=None, **kwargs):
        captured["cmd"] = cmd
        captured["env"] = env

        class R:
            returncode = 0
            stdout = "dryrun child: OK"
            stderr = ""

        return R()

    monkeypatch.setenv("JAX_PLATFORMS", "broken")
    monkeypatch.setenv(
        "XLA_FLAGS",
        "--xla_cpu_foo=1 --xla_force_host_platform_device_count=2",
    )
    monkeypatch.setattr(subprocess, "run", fake_run)
    graft.dryrun_multichip(8)

    env = captured["env"]
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "--xla_force_host_platform_device_count=8" in env["XLA_FLAGS"]
    # stale count from the parent must have been stripped, other flags kept
    assert "--xla_force_host_platform_device_count=2" not in env["XLA_FLAGS"]
    assert "--xla_cpu_foo=1" in env["XLA_FLAGS"]
    assert captured["cmd"][1].endswith("__graft_entry__.py")
    assert captured["cmd"][2:] == ["--dryrun-child", "8"]


def test_dryrun_child_failure_surfaces(monkeypatch):
    def fake_run(cmd, env=None, **kwargs):
        class R:
            returncode = 3
            stdout = "partial output"
            stderr = "boom"

        return R()

    monkeypatch.setattr(subprocess, "run", fake_run)
    with pytest.raises(RuntimeError, match="rc=3"):
        graft.dryrun_multichip(4)
