"""Share of its roofline latent attention's prefill reaches
(``lib/span_roofline.py``): the least time of the window's prefills by
``families/<family>/kernel_costs.py`` ``mla_prefill_cost``, from each
span's ``attn_key_rows`` (the keys its live rows see: the causal half,
live positions only, so a padded rung's extra tiles count against the
kernel) and ``tokens``, over the device time of ``flash_attention_fwd``."""
import importlib

from benchmarks.lib import span_roofline


def read(obs):
    cfg = obs.get("config", {})
    if "kv_lora_rank" not in cfg:
        return None
    costs = importlib.import_module(
        f"benchmarks.families.{cfg['family']}.kernel_costs")

    def cost(a):
        if not a.get("attn_key_rows") or not a.get("tokens"):
            return None
        return costs.mla_prefill_cost(cfg, a["attn_key_rows"], a["tokens"])
    return span_roofline.read(obs, "generation.prefill",
                              costs.PREFILL_KERNELS, cost)
