"""Milliseconds per serving-engine step in which the device ran nothing
while the host was inside ``serve.submit`` or ``serve.admit`` (a request's
sampling stream, admission and its prefills), in the traced window."""

SPANS = ("serve.submit", "serve.admit")


def read(w):
    t, steps = w.get("trace"), (w.get("counters") or {}).get("serve.steps")
    if not t or not steps or not any(n in t["spans"] for n in SPANS):
        return None
    return 1e3 * sum(t["spans"][n]["idle_s"] for n in SPANS
                     if n in t["spans"]) / steps
