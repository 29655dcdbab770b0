"""Per request (end_t - first_token_t) / (tokens - 1): the gap a streaming
caller sees between tokens, which a fused burst cannot hide.  95th percentile
over every request of the window; one that did not end ok counts as the worst."""

from chipbench.common import percentile


def read(run):
    if run.kind != "serve" or not run.records:
        return None
    worst = run.window_s
    gaps = [(r["end_t"] - r["first_token_t"]) / (r["tokens"] - 1)
            if r["status"] == "ok" and r["tokens"] > 1 else worst for r in run.records]
    gaps += [worst] * (run.attempted - len(gaps))
    return 1e3 * percentile(gaps, 95), {"samples": len(gaps),
                                        "p50_ms": round(1e3 * percentile(gaps, 50), 3)}
