"""Share of the device's busy time the block-sparse layers' mechanism
takes: their two attention kernels (``flash_attention_sparse_fwd`` in a
prefill, ``paged_attention_sparse_decode`` in a decode step) and the
selection's scoring and choice, which run in XLA and which the trace names
only by the loops that hold them (``kernel_costs.SELECTION_OPS``: a
prefill scores and chooses 256 query positions a pass of one loop, and
the choice's k-th largest score is 32 passes of another). A decode step's
scoring of one row a slot lies outside any loop and is not in this share.
Read beside ``attn.linear_busy_pct.tput``: what the two mechanisms cost."""
import importlib


def read(obs):
    tr, cfg = obs.get("trace"), obs.get("config", {})
    if not tr or "mixer_types" not in cfg or not tr.get("busy_s"):
        return None
    costs = importlib.import_module(
        f"benchmarks.families.{cfg['family']}.kernel_costs")
    kernels = costs.SPARSE_PREFILL_KERNELS + costs.SPARSE_DECODE_KERNELS
    ops = tr.get("by_op_s", {})
    if not any(n in ops for n in kernels):
        return None
    return 100.0 * sum(ops.get(n, 0.0) for n in
                       kernels + costs.SELECTION_OPS) / tr["busy_s"]
