"""Share of its roofline the block-sparse layers' decode attention reaches
(``lib/span_roofline.py``): the least time of the window's decode steps by
``families/<family>/kernel_costs.py`` ``sparse_decode_cost``, from each
step's ``selected_tokens`` (the rows its live slots' lists name, read once
a sparse layer) and ``cache_row_bytes`` (one layer's K and V of one token)
on the ``generation.decode_step`` span, over the device time of
``paged_attention_sparse_decode``."""
import importlib

from benchmarks.lib import span_roofline


def read(obs):
    cfg = obs.get("config", {})
    if "mixer_types" not in cfg:
        return None
    costs = importlib.import_module(
        f"benchmarks.families.{cfg['family']}.kernel_costs")

    def cost(a):
        if not a.get("selected_tokens") or not a.get("cache_row_bytes"):
            return None
        return costs.sparse_decode_cost(cfg, a["selected_tokens"],
                                        a["cache_row_bytes"])
    return span_roofline.read(obs, "generation.decode_step",
                              costs.SPARSE_DECODE_KERNELS, cost)
