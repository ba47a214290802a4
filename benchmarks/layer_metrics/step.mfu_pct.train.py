"""Model FLOP/s utilisation: the configuration's analytic forward+backward
FLOPs per sample times the measured samples/s over the chip's bf16 peak."""
from benchmarks.lib import peaks


def read(obs):
    if obs.get("kind") != "fit_cycle":
        return None
    return peaks.mfu_pct(obs["flops_per_sample"], obs["rate"],
                         obs["device"]["kind"], 1)
