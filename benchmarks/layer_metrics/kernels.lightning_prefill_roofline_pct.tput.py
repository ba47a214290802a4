"""Share of its roofline the lightning-attention layers' prefill reaches
(``lib/span_roofline.py``): the least time of the window's prefills by
``families/<family>/kernel_costs.py`` ``lightning_prefill_cost``, from each
span's ``linear_rows`` (its live positions: the recurrence's products and
q, k, v, o once a layer, so a padded rung's chunks and a chunked form's
extra products count against the kernel), over the device time of
``lightning_attention_fwd``."""
import importlib

from benchmarks.lib import span_roofline


def read(obs):
    cfg = obs.get("config", {})
    if "mixer_types" not in cfg:
        return None
    costs = importlib.import_module(
        f"benchmarks.families.{cfg['family']}.kernel_costs")

    def cost(a):
        if not a.get("linear_rows"):
            return None
        return costs.lightning_prefill_cost(cfg, a["linear_rows"])
    return span_roofline.read(obs, "generation.prefill",
                              costs.LIGHTNING_PREFILL_KERNELS, cost)
