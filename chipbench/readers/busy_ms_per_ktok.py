"""Device-busy time of the traced wave per thousand tokens processed."""


def read(run):
    if run.kind != "serve" or run.trace is None:
        return None
    processed = run.prompt_tokens + run.generated_ok
    return 1e3 * run.trace.busy_s / (processed / 1e3), {"busy_s": round(run.trace.busy_s, 4)}
