"""Seconds an iteration spends in the allgather-swap round trip (``IterationStats.reshard["wall_s"]``), the mean over the window's
iterations (host clock, each round ending in a read of its outputs)."""


def read(w):
    its = w.get("iterations")
    if not its:
        return None
    return sum(i["reshard_s"] for i in its) / len(its)
