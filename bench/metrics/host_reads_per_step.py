"""Blocking reads of device results to the host per serving-engine step
over the window: the increase of the engine's ``serve.host_reads`` counter
over that of ``serve.steps``."""


def read(w):
    c = w.get("counters") or {}
    if not c.get("serve.steps") or "serve.host_reads" not in c:
        return None
    return c["serve.host_reads"] / c["serve.steps"]
