"""lm.mfu: model FLOP utilization of the LM train step, in %.

Model FLOPs of one step, counted from shapes (``bench/work_lm.py``
``train_step``: MLA projections and causal attention, the dense layer, the
router, shared experts and the rows routed to the held experts, the latent
PQ term and the head, forward and backward, no recomputation; Adam and
the GCD steps), times the traced steps, over the traced seconds and the
chip's bfloat16 peak.
"""


def read(run, reduced):
    v = run.values
    steps = v.get("traced_units", v.get("steps"))
    if not steps or "step_flops" not in v:
        return None
    seconds = v.get("traced_s", v["window_s"])
    return 100.0 * v["step_flops"] * steps / seconds / run.peaks.bf16_flops
