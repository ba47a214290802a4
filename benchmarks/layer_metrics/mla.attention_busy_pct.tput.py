"""Share of the device's busy time inside latent attention's two kernels
(``flash_attention_fwd``, the expanded form a prefill runs, and
``paged_attention_latent_decode``, the absorbed form a decode step runs
over the cached rows): whether the mechanism carries the cell. Read
beside ``breakdown.device_ops``, which gives the experts' kernels the
same way."""
import importlib


def read(obs):
    tr, cfg = obs.get("trace"), obs.get("config", {})
    if not tr or "kv_lora_rank" not in cfg or not tr.get("busy_s"):
        return None
    costs = importlib.import_module(
        f"benchmarks.families.{cfg['family']}.kernel_costs")
    ops = tr.get("by_op_s", {})
    names = costs.DECODE_KERNELS + costs.PREFILL_KERNELS
    if not any(n in ops for n in names):
        return None
    return 100.0 * sum(ops.get(n, 0.0) for n in names) / tr["busy_s"]
