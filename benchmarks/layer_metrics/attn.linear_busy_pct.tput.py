"""Share of the device's busy time inside the lightning-attention layers'
two kernels (``lightning_attention_fwd`` in a prefill, ``lightning_decode``
in a decode step). Read beside ``attn.sparse_busy_pct.tput``."""
import importlib


def read(obs):
    tr, cfg = obs.get("trace"), obs.get("config", {})
    if not tr or "mixer_types" not in cfg or not tr.get("busy_s"):
        return None
    costs = importlib.import_module(
        f"benchmarks.families.{cfg['family']}.kernel_costs")
    names = costs.LIGHTNING_PREFILL_KERNELS + costs.LIGHTNING_DECODE_KERNELS
    ops = tr.get("by_op_s", {})
    if not any(n in ops for n in names):
        return None
    return 100.0 * sum(ops.get(n, 0.0) for n in names) / tr["busy_s"]
