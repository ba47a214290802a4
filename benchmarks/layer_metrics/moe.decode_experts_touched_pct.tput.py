"""Distinct experts a decode step touches over all it could (experts x
expert layers), mean over the window's ``generation.decode_step`` spans
that carry ``experts_touched``: each touched expert's three matrices are
read once a step, so this share of the held expert weights is decode's
floor in bytes."""
import importlib

from benchmarks.lib import readers


def read(obs):
    cfg = obs.get("config", {})
    if obs.get("kind") != "closed_loop" or "num_experts" not in cfg:
        return None
    sets = importlib.import_module(
        f"benchmarks.families.{cfg['family']}.kernel_costs").expert_sets(cfg)
    shares = [100.0 * s["args"]["experts_touched"] / sets
              for s in readers.spans(obs, "generation.decode_step")
              if "experts_touched" in s["args"]]
    return sum(shares) / len(shares) if len(shares) >= 10 else None
