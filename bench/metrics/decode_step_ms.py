"""Milliseconds per serving-engine step: the window over the increase of
the engine's ``serve.steps`` counter."""


def read(w):
    if w.get("kind") != "rollout" or not w["counters"]["steps"]:
        return None
    return 1e3 * w["window_s"] / w["counters"]["steps"]
