"""Submit to first host-visible token, 95th percentile over every request of
the window, from the engine's exact per-request records.  A request that did
not end ok, or has no first token, counts as the worst: the whole window."""

from chipbench.common import percentile


def read(run):
    if run.kind != "serve" or not run.records:
        return None
    worst = run.window_s
    waits = [r["first_token_t"] - r["submit_t"]
             if r["status"] == "ok" and r["first_token_t"] is not None else worst
             for r in run.records]
    waits += [worst] * (run.attempted - len(waits))
    return 1e3 * percentile(waits, 95), {"samples": len(waits),
                                         "p50_ms": round(1e3 * percentile(waits, 50), 3)}
