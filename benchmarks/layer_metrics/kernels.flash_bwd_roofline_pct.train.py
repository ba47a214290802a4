"""Share of its roofline the flash attention backward pass reaches, the
dq and the dk/dv kernel together: the least time the chip could take over
the backward calls in the traced window over the device time of
``flash_attention_bwd_dq`` + ``flash_attention_bwd_dkv``. At 8 x 1024
tokens and 16 heads of 64 in bfloat16 the compute bound binds (174.6 us a
call at the bf16 peak against 144.7 us at the memory's)."""
from benchmarks.lib import kernel_costs


def read(obs):
    if obs.get("kind") != "fit_cycle":
        return None
    return kernel_costs.train_roofline_pct(obs, kernel_costs.FLASH_BWD,
                                           kernel_costs.flash_bwd_cost)
