"""Milliseconds of the serving engine's own host work per step: the self
time, in the traced window, of its phase spans (each span's time less what
its child spans on the same thread cover), over the window's steps.  The
spans in which the host waits for the device (``serve.first_token``,
``serve.decode.wait``) are left out."""

SPANS = ("serve.step", "serve.submit", "serve.admit", "serve.prefill",
         "serve.decode.prep", "serve.decode.launch", "serve.retire")


def read(w):
    t, steps = w.get("trace"), (w.get("counters") or {}).get("serve.steps")
    if not t or not steps or "serve.step" not in t["spans"]:
        return None
    return 1e3 * sum(t["spans"][n]["self_s"] for n in SPANS
                     if n in t["spans"]) / steps
