"""Tokens of completed steps over all the window's time, the whole job."""


def read(run):
    if run.kind != "train":
        return None
    return run.tokens / run.window_s, {"steps": run.steps, "tokens_per_step": run.tokens_per_step}
