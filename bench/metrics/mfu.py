"""Model FLOPs of the window (``flops.py``: pads left out, attention in,
recomputation not counted) over window x chips x the chip's bf16 peak."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import peaks  # noqa: E402


def read(w):
    peak = peaks.peaks_for(w["device_kind"])["bf16_flops_per_s"]
    its = w.get("iterations")
    if its:
        work, secs = sum(i["flops"] for i in its), sum(i["wall_s"] for i in its)
    elif w.get("flops"):
        work, secs = w["flops"], w["window_s"]
    else:
        return None
    return 100.0 * work / (secs * w["chips"] * peak)
