"""Generated tokens of requests that ended ok, over all the window's time."""


def read(run):
    if run.kind != "serve":
        return None
    return run.generated_ok / run.window_s, {"tokens": run.generated_ok, "requests": run.attempted,
                                             "waves": run.waves}
