"""Share of its roofline the lightning-attention layers' decode step
reaches (``lib/span_roofline.py``): the least time of the window's decode
steps by ``families/<family>/kernel_costs.py`` ``lightning_decode_cost``,
from each step's ``state_bytes`` (its live slots' float32 states, every
layer: read once and written once) and ``slots`` on the
``generation.decode_step`` span, over the device time of
``lightning_decode``."""
import importlib

from benchmarks.lib import span_roofline


def read(obs):
    cfg = obs.get("config", {})
    if "mixer_types" not in cfg:
        return None
    costs = importlib.import_module(
        f"benchmarks.families.{cfg['family']}.kernel_costs")

    def cost(a):
        if not a.get("state_bytes") or not a.get("slots"):
            return None
        return costs.lightning_decode_cost(cfg, a["slots"], a["state_bytes"])
    return span_roofline.read(obs, "generation.decode_step",
                              costs.LIGHTNING_DECODE_KERNELS, cost)
