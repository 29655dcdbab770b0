"""Tokens processed (prompt and generated) per forward pass: how full the
scheduler keeps a step.  A burst of k steps is k forward passes."""


def read(run):
    if run.kind != "serve" or not run.forwards:
        return None
    processed = run.prompt_tokens + run.generated_ok
    return processed / run.forwards, {"tokens": processed, "forwards": run.forwards}
