"""Share of the rows the MoE grouped matmul computes that are padding:
(``serve.moe.padded_rows`` - ``serve.moe.rows``) / ``serve.moe.padded_rows``
over the window, from the engine's counters."""


def read(w):
    c = w.get("counters") or {}
    padded = c.get("serve.moe.padded_rows")
    if not padded:
        return None
    return 100.0 * (padded - c["serve.moe.rows"]) / padded
