"""mfu.train: the training step's model FLOPs (6 per parameter per token
plus causal attention, remat not counted; ``work.train_step_flops``) of
the steps that ran in the traced window, over the window times the chips'
bf16 peak."""


def read(x):
    t = x["trace"]
    if not x.get("train_flops") or not t["window_s"]:
        return None
    peak = x["peaks"]["bf16_flops_per_s"] * x["chips"]
    return 100.0 * x["train_flops"] / (t["window_s"] * peak)
