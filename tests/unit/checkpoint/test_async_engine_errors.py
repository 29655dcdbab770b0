"""AsyncCheckpointEngine error-channel regression tests (ISSUE 18).

The dslint cross-thread-mutation rule caught a real race here: the worker
thread stored ``self._error = exc`` while the caller side ran the unlocked
swap ``exc, self._error = self._error, None`` — a worker store landing
between the swap's read and its ``None`` write was silently discarded, so a
failed checkpoint write could vanish without ever being raised.  The fix
guards both sides with ``_error_lock``; these tests pin the contract.
"""

import threading

import numpy as np
import pytest

import deepspeed_tpu.runtime.checkpoint_engine.checkpoint_engine as ce_mod
from deepspeed_tpu.runtime.checkpoint_engine import AsyncCheckpointEngine


def drained(eng, timeout=30.0):
    """``eng._queue.join()`` held to ``timeout`` (``Queue.join`` takes none)."""
    with eng._queue.all_tasks_done:
        assert eng._queue.all_tasks_done.wait_for(lambda: not eng._queue.unfinished_tasks, timeout), \
            "the worker never emptied its queue"


@pytest.fixture
def failing_save(monkeypatch):
    calls = {"n": 0}

    def flaky(path, arr):
        calls["n"] += 1
        raise OSError(f"mount flaked ({calls['n']})")

    monkeypatch.setattr(ce_mod.np, "save", flaky)
    return calls


def test_worker_failure_surfaces_with_original_type(tmp_path, failing_save):
    eng = AsyncCheckpointEngine()
    eng.save(np.zeros(4), str(tmp_path / "a.npy"))
    with pytest.raises(OSError, match="mount flaked"):
        eng.flush()
    # the error channel is cleared by the raise: a retried flush is clean
    eng.flush()


def test_save_reraises_pending_error_before_enqueueing(tmp_path, failing_save):
    eng = AsyncCheckpointEngine()
    eng.save(np.zeros(4), str(tmp_path / "a.npy"))
    drained(eng)
    with pytest.raises(OSError):
        eng.save(np.zeros(4), str(tmp_path / "b.npy"))


def test_error_raised_exactly_once_across_concurrent_drains(tmp_path,
                                                            failing_save):
    """The race the lint caught: N threads draining the error channel while
    the worker may store into it must hand the error to exactly one of them
    (the unlocked swap could lose it to a torn read-then-None-write)."""
    eng = AsyncCheckpointEngine()
    eng.save(np.zeros(4), str(tmp_path / "a.npy"))
    drained(eng)

    raised = []
    raised_lock = threading.Lock()
    barrier = threading.Barrier(8, timeout=30)

    def drain():
        barrier.wait()
        try:
            eng._raise_pending()
        except OSError as exc:
            with raised_lock:
                raised.append(exc)

    threads = [threading.Thread(target=drain) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)
    assert len(raised) == 1
    assert "mount flaked" in str(raised[0])


def test_error_survives_until_raised_never_lost(tmp_path, failing_save):
    """Every failed write is eventually reported: drive K failing saves with
    an interleaved reader loop and count one raise per stored error."""
    eng = AsyncCheckpointEngine(max_queue=2)
    reported = 0
    for i in range(20):
        try:
            eng.save(np.zeros(2), str(tmp_path / f"{i}.npy"))
        except OSError:
            reported += 1
        drained(eng)
    try:
        eng.flush()
    except OSError:
        reported += 1
    # every enqueued save failed; each failure is surfaced exactly once, and
    # the final flush leaves the channel clean
    assert reported == failing_save["n"]
    eng.flush()
    eng.close()
