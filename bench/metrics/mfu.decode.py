"""mfu.decode: model FLOPs of the tokens served in the traced window (every
decoded token at its context, every prompt whose prefill completed;
``work.token_flops``/``work.prefill_flops``), over the window times the
bf16 peak."""


def read(x):
    t = x["trace"]
    if not x.get("model_flops") or not t["window_s"]:
        return None
    peak = x["peaks"]["bf16_flops_per_s"] * x["chips"]
    return 100.0 * x["model_flops"] / (t["window_s"] * peak)
