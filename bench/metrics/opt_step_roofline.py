"""opt_step_roofline: the fused LOTION-AdamW kernel's share of its
roofline.  The least time is the bytes the update needs (28 B/param:
float32 weights, gradients and both moments read, weights and moments
written; ``work.opt_step_bytes``) over HBM bandwidth, for the steps traced;
the time is the summed device duration of the ``opt_step`` events."""


def read(x):
    k = x["trace"]["kernel_s"].get("opt_step")
    if not k or not x.get("opt_step_bound_s"):
        return None
    return 100.0 * x["opt_step_bound_s"] / k
