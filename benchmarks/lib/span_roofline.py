"""Share of its roofline a kernel reaches over the program calls of the
traced part of a serving window, for readers whose kernel's work is
counted from what a span carries: the least time the chip could take over
the calls (each the larger of FLOPs / bf16 peak and bytes / memory
bandwidth) over the device time the trace gives the kernel. The spans of
the whole window give the rate at which that least time accrues; the
traced part is ``window_s`` of it (the load is steady at saturation), as
``kernels.moe_experts_roofline_pct.tput`` takes it."""
from typing import Callable, Dict, Optional, Sequence, Tuple

from benchmarks.lib import peaks, readers


def read(obs: Dict, span: str, kernels: Sequence[str],
         cost: Callable[[Dict], Optional[Tuple[float, float]]]
         ) -> Optional[float]:
    """``cost(args)`` gives (FLOPs, bytes) of one call from its span's
    attributes, or None where the span does not carry them. None where the
    trace names none of ``kernels`` or no span can be costed."""
    tr = obs.get("trace")
    if obs.get("kind") != "closed_loop" or not tr:
        return None
    seconds = sum(tr.get("by_op_s", {}).get(n, 0.0) for n in kernels)
    if not seconds:
        return None
    p = peaks.peak(obs["device"]["kind"])
    least = 0.0
    for s in readers.spans(obs, span):
        c = cost(s["args"])
        if c is not None:
            # a span cut by the window's edge counts by the part inside
            least += max(c[0] / p["bf16_flops"], c[1] / p["hbm_bytes_per_s"]) \
                * s["clipped"] / s["dur"]
    if not least:
        return None
    return 100.0 * least / readers.window_seconds(obs) * tr["window_s"] / seconds
