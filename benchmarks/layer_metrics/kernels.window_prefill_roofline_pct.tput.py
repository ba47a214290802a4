"""Share of its roofline the sliding-window layers' prefill attention
reaches (``lib/span_roofline.py``): the least time of the window's
prefills by ``families/<family>/kernel_costs.py`` ``window_prefill_cost``,
from each span's ``attn_window_key_rows`` (the keys its live rows see
INSIDE their windows, so a kernel that walks older tiles, and a padded
rung's extra tiles, count against the kernel) and ``tokens``, over the
device time of ``flash_attention_window_fwd``."""
import importlib

from benchmarks.lib import span_roofline


def read(obs):
    cfg = obs.get("config", {})
    if "sliding_window" not in cfg:
        return None
    costs = importlib.import_module(
        f"benchmarks.families.{cfg['family']}.kernel_costs")

    def cost(a):
        if not a.get("attn_window_key_rows") or not a.get("tokens"):
            return None
        return costs.window_prefill_cost(cfg, a["attn_window_key_rows"],
                                         a["tokens"])
    return span_roofline.read(obs, "generation.prefill",
                              costs.WINDOW_PREFILL_KERNELS, cost)
