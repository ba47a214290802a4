"""Share of their roofline the experts' matmuls reach: the least time the
chip could take over the calls of the traced part of the window over the
device time the trace gives ``moe_experts_gate_up`` and
``moe_experts_down``. The least time of one program call is the larger of
FLOPs / bf16 peak and bytes / memory bandwidth, by
``families/lfm2_moe/kernel_costs.py`` from the pairs routed and the
experts touched that its span carries (a prefill is compute bound, a
decode step streams the touched experts' weights). The spans of the whole
window give the rate at which that least time accrues; the traced part is
``window_s`` of it (the load is steady at saturation), as
``lib/kernel_costs.train_roofline_pct`` takes a training step's calls."""
import importlib

from benchmarks.lib import peaks, readers


def read(obs):
    tr, cfg = obs.get("trace"), obs.get("config", {})
    if obs.get("kind") != "closed_loop" or not tr or "num_experts" not in cfg:
        return None
    costs = importlib.import_module(
        f"benchmarks.families.{cfg['family']}.kernel_costs")
    if any(n not in tr.get("by_op_s", {}) for n in costs.EXPERT_KERNELS):
        return None
    p = peaks.peak(obs["device"]["kind"])
    least = 0.0
    for name in ("generation.prefill", "generation.decode_step"):
        for s in readers.spans(obs, name):
            a = s["args"]
            if not a.get("moe_pairs") or "experts_touched" not in a:
                continue
            flops, nbytes = costs.experts_cost(cfg, a["moe_pairs"],
                                               a["experts_touched"])
            # a span cut by the window's edge counts by the part inside
            least += max(flops / p["bf16_flops"],
                         nbytes / p["hbm_bytes_per_s"]) \
                * s["clipped"] / s["dur"]
    if not least:
        return None
    seconds = sum(tr["by_op_s"][n] for n in costs.EXPERT_KERNELS)
    return 100.0 * least / readers.window_seconds(obs) * tr["window_s"] / seconds
