"""Seconds an iteration spends in the old and reference log-probability stage (``stage_times["infer"]``), the mean over the window's
iterations (host clock, each round ending in a read of its outputs)."""


def read(w):
    its = w.get("iterations")
    if not its:
        return None
    return sum(i["infer_s"] for i in its) / len(its)
