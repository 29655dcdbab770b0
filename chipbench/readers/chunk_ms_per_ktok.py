"""Device time of the forward programs that hold a prompt chunk, per thousand
prompt tokens of the traced wave.  The program says its bucket in its name
(``jit_fwd_n32_t256_b20``): a chunk is ``t`` > 1, whether the step is pure
prefill or mixes decode rows in; bursts, picks and one-token steps are left
out.  Where no program is named so (an older program names every bucket
``jit_fwd``), there is nothing to read."""

import re

BUCKET = re.compile(r"fwd_n(\d+)_t(\d+)_b(\d+)")


def read(run):
    if run.kind != "serve" or run.trace is None or not run.prompt_tokens:
        return None
    first = next(iter(run.trace.devices.values()))
    by_bucket = {}
    for name, _, duration in first["modules"]:
        found = BUCKET.search(name)
        if found and int(found.group(2)) > 1:
            runs, ns = by_bucket.get(found.group(0), (0, 0))
            by_bucket[found.group(0)] = (runs + 1, ns + duration)
    if not by_bucket:
        return None
    seconds = sum(ns for _, ns in by_bucket.values()) / 1e9
    top = sorted(by_bucket.items(), key=lambda kv: -kv[1][1])[:5]
    return 1e3 * seconds / (run.prompt_tokens / 1e3), {
        "chunk_programs_run": sum(runs for runs, _ in by_bucket.values()),
        "chunk_s": round(seconds, 4), "prompt_tokens": run.prompt_tokens,
        "top": ",".join(f"{name}:{runs}x:{ns / 1e9:.4f}s" for name, (runs, ns) in top)}
