"""Share of its roofline the block-sparse layers' prefill attention
reaches (``lib/span_roofline.py``): the least time of the window's
prefills by ``families/<family>/kernel_costs.py`` ``sparse_prefill_cost``,
from each span's ``attn_selected_key_rows`` (the keys its live rows' lists
name, so score tiles a kernel computes beyond them, and a padded rung's
tiles, count against the kernel) and ``tokens``, over the device time of
``flash_attention_sparse_fwd``."""
import importlib

from benchmarks.lib import span_roofline


def read(obs):
    cfg = obs.get("config", {})
    if "mixer_types" not in cfg:
        return None
    costs = importlib.import_module(
        f"benchmarks.families.{cfg['family']}.kernel_costs")

    def cost(a):
        if not a.get("attn_selected_key_rows") or not a.get("tokens"):
            return None
        return costs.sparse_prefill_cost(cfg, a["attn_selected_key_rows"],
                                         a["tokens"])
    return span_roofline.read(obs, "generation.prefill",
                              costs.SPARSE_PREFILL_KERNELS, cost)
