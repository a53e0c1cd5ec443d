"""Integration tests for the mesh runtime: the train/serve drivers run end
to end on simulated multi-device meshes (subprocesses keep the main pytest
process at 1 device)."""
import os
import subprocess
import sys

import pytest


def _run(cmd, timeout=560):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, "-m"] + cmd, env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_train_driver_8dev_diloco():
    r = _run(["repro.launch.train", "--arch", "granite-3-8b", "--smoke",
              "--devices", "8", "--clusters", "2", "--data", "2",
              "--model", "2", "--rounds", "3", "--h-steps", "4",
              "--global-batch", "8", "--seq-len", "32"])
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "TRAIN-DRIVER-OK" in r.stdout
    # losses should be finite and logged per round
    assert r.stdout.count("round ") == 3


@pytest.mark.slow
def test_train_driver_adaptive():
    r = _run(["repro.launch.train", "--arch", "gemma3-1b", "--smoke",
              "--devices", "4", "--clusters", "2", "--data", "1",
              "--model", "2", "--rounds", "3", "--h-steps", "3",
              "--global-batch", "4", "--seq-len", "32", "--adaptive",
              "--rank", "8"])
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "TRAIN-DRIVER-OK" in r.stdout


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["gemma3-1b", "zamba2-1.2b"])
def test_serve_driver(arch):
    r = _run(["repro.launch.serve", "--arch", arch, "--smoke",
              "--devices", "4", "--data", "2", "--model", "2",
              "--batch", "4", "--prompt-len", "8",
              "--gen-len", "8"])
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "SERVE-DRIVER-OK" in r.stdout
    # satellite: throughput is now reported per phase + the combined line
    assert "prefill: " in r.stdout and "decode: " in r.stdout
    assert "generated shape" in r.stdout


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["gemma3-1b", "granite-3-8b"])
def test_serve_driver_paged(arch):
    r = _run(["repro.launch.serve", "--arch", arch, "--smoke",
              "--devices", "4", "--data", "2", "--model", "2",
              "--batch", "3", "--prompt-len", "8",
              "--gen-len", "8", "--paged", "--requests", "6"])
    assert r.returncode == 0, r.stdout[-1500:] + r.stderr[-1500:]
    assert "SERVE-DRIVER-OK" in r.stdout
    assert "paged engine: 6 requests" in r.stdout
    assert "admission fingerprint:" in r.stdout


@pytest.mark.slow
def test_serve_driver_paged_unsupported_family():
    # zamba2 is a hybrid SSM stack: the paged engine must refuse cleanly
    r = _run(["repro.launch.serve", "--arch", "zamba2-1.2b", "--smoke",
              "--devices", "4", "--data", "2", "--model", "2",
              "--batch", "2", "--prompt-len", "4",
              "--gen-len", "4", "--paged"])
    assert r.returncode == 2, r.stdout[-1500:] + r.stderr[-1500:]
    assert "SERVE-DRIVER-UNSUPPORTED" in r.stdout
