"""1 - (union of the device's operation intervals) / traced window, for the
device that idles most."""


def read(run):
    if run.trace is None:
        return None
    shares = run.trace.idle_share_by_device()
    return 100.0 * max(shares.values()), {"devices": len(shares),
                                          "window_s": round(run.trace.window_s, 4)}
