"""The MoE grouped matmul's share of its roofline: the least time the
expert work the window's ``serve.moe.*`` counters record needs on the chip
(the configuration's family ``gmm_cost``: the larger of FLOPs over peak
FLOP/s and bytes over peak bandwidth, over the window's totals) over the
device time of the kernel (``moe_gmm``) in the trace.

The window carries no configuration, so the sizes come from the family's
record of the configurations it built in this process (``BUILT``); where
that record holds more than one, the reader cannot tell which ran and
reads nothing."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import harness  # noqa: E402
import peaks  # noqa: E402

KERNEL = "moe_gmm"
FAMILY = "qwen3_moe"


def read(w):
    t, c = w.get("trace"), w.get("counters") or {}
    if not t or not c.get("serve.moe.calls"):
        return None
    spent = sum(s for n, s in t["op_s"].items() if KERNEL in n)
    fam = harness.family({"family": FAMILY})
    built = fam.BUILT
    if not spent or not built or any(b != built[0] for b in built):
        return None
    p = peaks.peaks_for(w["device_kind"])
    f, b = fam.gmm_cost(built[0], c)
    least = max(f / p["bf16_flops_per_s"], b / p["hbm_bytes_per_s"])
    return 100.0 * least / spent
