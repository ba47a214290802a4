"""Share of its roofline the sliding-window layers' decode attention
reaches (``lib/span_roofline.py``): the least time of the window's decode
steps by ``families/<family>/kernel_costs.py`` ``window_decode_cost``,
from each step's ``window_tokens`` (the rows inside its live slots'
windows, read once a window layer) and ``cache_row_bytes`` (one layer's K
and V of one token) on the ``generation.decode_step`` span, over the
device time of ``paged_attention_window_decode``."""
import importlib

from benchmarks.lib import span_roofline


def read(obs):
    cfg = obs.get("config", {})
    if "sliding_window" not in cfg:
        return None
    costs = importlib.import_module(
        f"benchmarks.families.{cfg['family']}.kernel_costs")

    def cost(a):
        if not a.get("window_tokens") or not a.get("cache_row_bytes"):
            return None
        return costs.window_decode_cost(cfg, a["window_tokens"],
                                        a["cache_row_bytes"])
    return span_roofline.read(obs, "generation.decode_step",
                              costs.WINDOW_DECODE_KERNELS, cost)
