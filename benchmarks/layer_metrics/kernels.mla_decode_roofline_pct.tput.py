"""Share of its roofline the latent cache's decode attention reaches
(``lib/span_roofline.py``): the least time of the window's decode steps by
``families/<family>/kernel_costs.py`` ``mla_decode_cost``, from each
step's ``live_tokens`` (every live row read once a layer) and
``cache_row_bytes`` (a row as the pool lays it out) on the
``generation.decode_step`` span, over the device time of
``paged_attention_latent_decode``."""
import importlib

from benchmarks.lib import span_roofline


def read(obs):
    cfg = obs.get("config", {})
    if "kv_lora_rank" not in cfg:
        return None
    costs = importlib.import_module(
        f"benchmarks.families.{cfg['family']}.kernel_costs")

    def cost(a):
        if not a.get("live_tokens") or not a.get("cache_row_bytes"):
            return None
        return costs.mla_decode_cost(cfg, a["live_tokens"],
                                     a["cache_row_bytes"])
    return span_roofline.read(obs, "generation.decode_step",
                              costs.DECODE_KERNELS, cost)
