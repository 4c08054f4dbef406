"""The benchmark runs only on a chip it has peaks for: with no TPU it
exits non-zero and prints no result; a device kind missing from
bench/peaks.json is refused."""
import os
import sys
from types import SimpleNamespace

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import pytest

from bench import run


def test_refuses_a_non_tpu_device(capsys):
    rc = run.main(["--workload", "qwen-batch", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "needs a TPU" in out.err


def test_refuses_a_device_missing_from_the_peak_table(monkeypatch):
    fake = SimpleNamespace(platform="tpu", device_kind="TPU v0 imaginary")
    monkeypatch.setattr(run.jax, "devices", lambda: [fake])
    with pytest.raises(run.NoDevice, match="peaks"):
        run.check_device(1)


def test_refuses_too_few_chips(monkeypatch):
    fake = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    monkeypatch.setattr(run.jax, "devices", lambda: [fake])
    with pytest.raises(run.NoDevice, match="chips"):
        run.check_device(4)
    assert run.check_device(1)[1]["hbm_bytes_per_s"] == 819e9
