"""Real 2-process execution lane (VERDICT r2 #2).

Analog of the reference's DistributedTest harness (tests/unit/common.py:105):
N real ranks on one host, real collectives, no mocks.  Here: 2 JAX controller
processes x 4 CPU devices each, rendezvoused via jax.distributed — rank
discovery, host collectives, ZeRO-3 sharding across non-addressable devices,
and checkpoint save/load all run in their true multi-process regime.

Also covers the launcher's local spawn (reference launcher/launch.py:132).
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
WORKER = os.path.join(REPO, "tests", "unit", "mp_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _spawn(rank: int, port: int, tmp: str) -> subprocess.Popen:
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        "COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
        "WORLD_SIZE": "2",
        "RANK": str(rank),
        "MP_TMP": tmp,
        "PYTHONPATH": REPO + os.pathsep + env.get("PYTHONPATH", ""),
    })
    return subprocess.Popen([sys.executable, WORKER], env=env,
                            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


@pytest.mark.slow
def test_two_process_zero3_collectives_and_checkpoint(tmp_path):
    port = _free_port()
    procs = [_spawn(r, port, str(tmp_path)) for r in range(2)]
    outs = []
    for p in procs:
        try:
            out, _ = p.communicate(timeout=120)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            pytest.fail("2-process lane hung (120s timeout)")
        outs.append(out)
    for r, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {r} failed:\n{out[-3000:]}"
    # both ranks wrote success markers with IDENTICAL losses (SPMD consistency)
    results = []
    for r in range(2):
        marker = tmp_path / f"ok.rank{r}"
        assert marker.exists(), outs[r][-2000:]
        results.append(marker.read_text())
    assert results[0] == results[1], (results[0], results[1])
    assert "zero3_losses=" in results[0] and "ckpt_roundtrip_tag=" in results[0]
    # round-4 lane extensions (VERDICT r3 #8): cross-process TP serving +
    # compiled pipeline, the two comm patterns furthest from plain dp
    assert "tp8_v2_decode=" in results[0]
    assert "pipe2_cross_process=ok" in results[0]


def test_launcher_local_spawn(tmp_path):
    """bin/dstpu-style local launch runs the user script in-place
    (reference launcher/launch.py:132 local path)."""
    script = tmp_path / "user_script.py"
    script.write_text("import sys; print('user-script-ran'); sys.exit(0)\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-m", "deepspeed_tpu.launcher.runner",
                        str(script)], env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-1500:]
    assert "user-script-ran" in r.stdout
