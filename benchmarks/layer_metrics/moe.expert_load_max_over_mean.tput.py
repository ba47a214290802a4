"""Pairs of the fullest expert over the mean pairs an expert gets, mean over
the window's prefills: ``moe_load_max`` (the fullest expert of any expert
layer, live positions only) over ``moe_pairs`` / (expert layers x experts),
both on the ``generation.prefill`` span. 1 is a perfectly even load; the
row tiles of the fullest expert are the tail of the grouped matmul."""
import importlib

from benchmarks.lib import readers


def read(obs):
    cfg = obs.get("config", {})
    if obs.get("kind") != "closed_loop" or "num_experts" not in cfg:
        return None
    sets = importlib.import_module(
        f"benchmarks.families.{cfg['family']}.kernel_costs").expert_sets(cfg)
    ratios = [s["args"]["moe_load_max"] * sets / s["args"]["moe_pairs"]
              for s in readers.spans(obs, "generation.prefill")
              if s["args"].get("moe_pairs") and "moe_load_max" in s["args"]]
    return sum(ratios) / len(ratios) if len(ratios) >= 10 else None
