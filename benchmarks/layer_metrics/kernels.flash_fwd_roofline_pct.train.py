"""Share of its roofline the flash attention forward kernel reaches: the
least time the chip could take over the forward calls in the traced window
over the device time of ``flash_attention_fwd``. At 8 x 1024 tokens and 16
heads of 64 in bfloat16 the compute bound binds (87.3 us a call at the
bf16 peak against 82.6 us at the memory's)."""
from benchmarks.lib import kernel_costs


def read(obs):
    if obs.get("kind") != "fit_cycle":
        return None
    return kernel_costs.train_roofline_pct(obs, kernel_costs.FLASH_FWD,
                                           kernel_costs.flash_fwd_cost)
