"""``chip_smoke.py`` on the CPU: the rehearsal runs every phase and never
claims a chip result, and a full run refuses to start without a TPU."""
import importlib.util
import json
from pathlib import Path

import pytest


@pytest.fixture(scope="module")
def chip_smoke():
    path = Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_rehearsal_runs_every_phase(chip_smoke, capsys):
    rc = chip_smoke.main(["--rehearse"])
    lines = capsys.readouterr().out.splitlines()
    assert rc == 0
    for phase in ("train", "serve", "kernel"):
        assert any(ln.startswith(f"{phase}: phase: passed") for ln in lines)
    assert not any("FAILED" in ln for ln in lines)
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["rehearsal"] is True
    assert last["device"]["platform"] == "cpu"


def test_full_run_needs_a_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    assert capsys.readouterr().out == ""
