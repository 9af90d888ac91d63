"""The paged decode-attention kernel's share of its roofline: the least
time its work needs on the chip (``flops.paged_attention_cost`` of every
traced step: the larger of FLOPs over peak FLOP/s and bytes over peak
bandwidth) over the kernel's device time in the trace."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import peaks  # noqa: E402

KERNEL = "paged_decode"


def read(w):
    t, steps = w.get("trace"), w.get("paged_attention")
    if not t or not steps:
        return None
    spent = sum(s for n, s in t["op_s"].items() if KERNEL in n)
    if not spent:
        return None
    p = peaks.peaks_for(w["device_kind"])
    least = sum(max(f / p["bf16_flops_per_s"], b / p["hbm_bytes_per_s"])
                for f, b in steps)
    return 100.0 * least / spent
