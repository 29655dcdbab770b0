"""How full the launched forward buckets were: tokens that advanced a sequence
over the token slots the programs computed (a step of 31 decode rows and one
225-token chunk is 32 x 256 slots for 256 tokens).  Both counts are the
program's own (``ServeCounters``); a program without them gives nothing."""


def read(run):
    if run.kind != "serve" or not run.counters.get("token_slots"):
        return None
    live, slots = run.counters["live_tokens"], run.counters["token_slots"]
    return 100.0 * live / slots, {"live_tokens": live, "token_slots": slots}
