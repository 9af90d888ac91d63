"""Tokens the serving engine generated in the window (first tokens and
decoded ones, of finished and unfinished requests alike) over the window,
per chip."""


def read(w):
    if w.get("kind") != "rollout":
        return None
    return w["counters"]["sampled_tokens"] / w["window_s"] / w["chips"]
