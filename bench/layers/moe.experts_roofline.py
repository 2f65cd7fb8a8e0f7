"""moe.experts_roofline: the held experts' grouped matmuls' share of their
roofline, in %.

The least time the rows routed to the held experts could take on the chip
(their FLOPs over the bfloat16 peak, or their bytes over peak HBM
bandwidth, whichever is larger; forward and backward, from the step's own
``moe.local_tokens``; ``bench/work_lm.py`` ``expert_rows``) over the device
time per traced step of the ops under ``jax.named_scope("moe.experts")``
(``models/moe.py``), read from the trainer's step program
(``bench/lm.py`` ``scope_ms``).
"""


def read(run, reduced):
    from bench import lm

    ms = lm.scope_ms(run, reduced, "moe.experts")
    v = run.values
    if not ms or "expert_flops" not in v:
        return None
    pk = run.peaks
    least = max(v["expert_flops"] / pk.bf16_flops,
                v["expert_bytes"] / pk.hbm_bytes_per_s)
    return 100.0 * least / (ms / 1e3)
