"""Programs the serving engine launched per step over the window: the
increase of its ``serve.launches`` counter over that of ``serve.steps``."""


def read(w):
    c = w.get("counters") or {}
    if not c.get("serve.steps") or "serve.launches" not in c:
        return None
    return c["serve.launches"] / c["serve.steps"]
