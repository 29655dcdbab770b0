"""Where the benchmark under test lies, and its command run in this process
at its rehearsal size.

``ROOT`` holds ``BENCHMARK.json`` and ``chipbench/``: the repository, or the
copy that ``CHIPBENCH_ROOT`` names, which only ``test_harness.py``'s guard sets
(it appends a cell and a metric to a copy and runs the ``reads_benchmark``
tests over it).  Every test file takes ``ROOT`` from here; a test finds a cell,
a configuration or a metric by its name, never by its place or a count."""

import importlib.util
import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
ROOT = os.path.abspath(os.environ.get("CHIPBENCH_ROOT") or REPO)
# the serving cells the benchmark held when PRs 35 and 42 listed their metrics in them: a metric's
# list holds these, and a serving cell added since may have joined it or stayed off it
SERVING_THEN = {"serve.chat-burst", "serve.decode-heavy", "serve.long-prompt", "serve.moe-chat-burst",
                "serve.mla-long-prompt", "serve.conv-chat-burst"}
if ROOT != REPO:  # the copy's ``chipbench`` package, wherever pytest puts the repository on the path
    spec = importlib.util.spec_from_file_location("chipbench", os.path.join(ROOT, "chipbench",
                                                                             "__init__.py"))
    sys.modules["chipbench"] = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(sys.modules["chipbench"])


def pytest_configure(config):
    config.addinivalue_line("markers", "reads_benchmark: reads BENCHMARK.json or the files it "
                            "names and launches nothing: the guard runs these over a grown copy")


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
