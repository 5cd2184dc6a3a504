"""decode_attn_paged_roofline: the paged int4 decode-attention kernel's
share of its roofline.  Least time: every decoded token's live context, its
int4 K and V codes and per-vector scales read once per layer, over HBM
bandwidth (``work.decode_attn_bound_s``); time: the summed device duration
of the ``decode_attn_paged`` events."""


def read(x):
    k = x["trace"]["kernel_s"].get("decode_attn_paged")
    if not k or not x.get("decode_attn_bound_s"):
        return None
    return 100.0 * x["decode_attn_bound_s"] / k
