"""Share of the device's busy time inside the sliding-window layers' two
attention kernels (``flash_attention_window_fwd`` in a prefill,
``paged_attention_window_decode`` in a decode step). Read beside
``attn.full_busy_pct.tput``: which kind of layer carries the cell."""
import importlib


def read(obs):
    tr, cfg = obs.get("trace"), obs.get("config", {})
    if not tr or "sliding_window" not in cfg or not tr.get("busy_s"):
        return None
    costs = importlib.import_module(
        f"benchmarks.families.{cfg['family']}.kernel_costs")
    names = costs.WINDOW_PREFILL_KERNELS + costs.WINDOW_DECODE_KERNELS
    ops = tr.get("by_op_s", {})
    if not any(n in ops for n in names):
        return None
    return 100.0 * sum(ops.get(n, 0.0) for n in names) / tr["busy_s"]
