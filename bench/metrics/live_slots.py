"""Slots that took part in an engine step, averaged over the window's
steps: running requests after the step and those that finished in it."""


def read(w):
    s = w.get("stream")
    if not s or not s["steps"]:
        return None
    return s["live_slots_mean"]
