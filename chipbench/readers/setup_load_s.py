"""Seconds in the backend before the window: the ``load`` rows of the
program's set-up account, an XLA compile where the persistent cache missed and
key + read + decompress + deserialise where it hit.  The note says which
reading it was: a warm one only where every load was a hit (``cache_misses``,
the loads without one, is 0)."""

from chipbench.reduce import setup_account


def read(run):
    found = setup_account.seconds(run, "load", "cache_hits", "cache_misses", "retrieval_s")
    if found is not None:
        found[1]["warm"] = found[1]["cache_hits"] == found[1]["rows"]
    return found
