"""Trained tokens (prompt and response of every sample, pads excluded) of
the window's whole GRPO iterations, over their total wall time, per chip."""


def read(w):
    its = w.get("iterations")
    if not its:
        return None
    return (sum(i["tokens"] for i in its) / sum(i["wall_s"] for i in its)
            / w["chips"])
