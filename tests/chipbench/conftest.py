"""Runs the benchmark's command in this process at its rehearsal size."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


class Rehearsal:
    def __init__(self, code, out):
        self.code, self.out = code, out
        line = [l for l in out.splitlines() if l.startswith("[rehearsal-not-a-result] ")]
        self.line = json.loads(line[-1].split(" ", 1)[1]) if line else None

    def number(self, name):
        found = re.findall(rf"\b{name}=([-+0-9.e]+)", self.out)
        assert found, f"{name} is not printed:\n{self.out}"
        return float(found[-1])


@pytest.fixture
def rehearse(monkeypatch, capfd):
    """``rehearse("--workload", ...)`` -> Rehearsal.  What run.py changes in
    the process (the interpreted-kernel switch, the environment) is put back."""
    from deepspeed_tpu.ops import _pallas
    from chipbench import run as command

    def go(*argv):
        monkeypatch.setattr(_pallas, "INTERPRET", _pallas.INTERPRET)
        for name in ("JAX_PLATFORMS", "XLA_FLAGS"):
            monkeypatch.setenv(name, os.environ.get(name, ""))
        capfd.readouterr()
        code = command.main(list(argv) + ["--rehearse"])
        return Rehearsal(code, capfd.readouterr().out)

    return go
