"""Seconds an iteration spends in the generation stage (``stage_times["gen"]``), the mean over the window's
iterations (host clock, each round ending in a read of its outputs)."""


def read(w):
    its = w.get("iterations")
    if not its:
        return None
    return sum(i["gen_s"] for i in its) / len(its)
