"""chip_smoke.py off the chip: it refuses a CPU-only process, any
failed phase withholds the final ``"ok": true`` line, and each phase's
chip side agrees with its CPU oracle.

The phases' chip side runs on the CPU device here; only the chip run
itself shows the TPU's results are bit-identical."""
from __future__ import annotations

import functools
import importlib.util
import json
import os
import sys

import jax
import pytest

from repro.kernels import convcore, postproc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    """The script as a module; its phases import ``benchmarks`` from
    the repo root."""
    sys.path.insert(0, REPO)
    try:
        spec = importlib.util.spec_from_file_location(
            "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        yield mod
    finally:
        sys.path.remove(REPO)


def test_refuses_without_a_tpu(smoke, capsys):
    assert jax.devices()[0].platform == "cpu"
    assert smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert out == ""
    assert "refusing" in err


def _raises(device, cpu):
    raise RuntimeError("forced failure")


def _disagrees(device, cpu):
    return {"seconds": 0.0, "compile_seconds": 0.0,
            "persistent_cache_hits": 0, "checks": {"bit_identical": False}}


@pytest.mark.parametrize("failing", [_raises, _disagrees],
                         ids=["raises", "check_fails"])
def test_failed_phase_stops_the_run(smoke, failing):
    lines, calls = [], []

    def never(device, cpu):
        calls.append(device)
        return {"checks": {}}

    ok = smoke.run([("bad", failing), ("after", never)],
                   jax.devices()[0], None, emit=lines.append)
    assert ok is False and calls == []
    (line,) = [json.loads(s) for s in lines]
    assert line["phase"] == "bad" and line["ok"] is False


@pytest.mark.parametrize("phase", ["fig5", "campaign", "farm", "conv"])
def test_phase_against_cpu_oracle_at_small_size(smoke, monkeypatch, phase):
    """The chip side and the oracle side of a phase, both on the CPU
    device, agree check for check (fig5 at full size, the others cut
    small)."""
    monkeypatch.setattr(smoke, "CAMPAIGN_POINTS", 16)
    monkeypatch.setattr(smoke, "CAMPAIGN_WINDOW", 256)
    monkeypatch.setattr(smoke, "FARM_NODES", 2)
    monkeypatch.setattr(smoke, "FARM_MAX_BURSTS", 256)
    monkeypatch.setattr(smoke, "CONV_HW", 32)
    # the Pallas kernels run on a CPU only in interpret mode
    monkeypatch.setattr(convcore, "conv2d_int8", functools.partial(
        convcore.conv2d_int8, interpret=True))
    monkeypatch.setattr(postproc, "postprocess", functools.partial(
        postproc.postprocess, interpret=True))
    cpu = jax.devices("cpu")[0]
    lines = []
    assert smoke.run([(phase, dict(smoke.PHASES)[phase])], cpu, cpu,
                     emit=lines.append)
    (line,) = [json.loads(s) for s in lines]
    assert line["ok"] and line["checks"] and all(line["checks"].values())
    assert line["oracle"] == "cpu device"

