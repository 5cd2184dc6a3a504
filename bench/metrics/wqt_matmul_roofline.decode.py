"""wqt_matmul_roofline.decode: the int4 weight matmul kernel's share of its
roofline.  Least time: per layer matrix and per pass (each decode step
over every slot row, each prefill chunk over its width), the larger of its
FLOPs over the bf16 peak and its bytes (int4 codes, scale, bf16 rows in
and out) over HBM bandwidth (``work.wqt_matmul_bound_s``); time: the
summed device duration of the ``wqt_matmul`` events."""


def read(x):
    k = x["trace"]["kernel_s"].get("wqt_matmul")
    if not k or not x.get("wqt_matmul_bound_s"):
        return None
    return 100.0 * x["wqt_matmul_bound_s"] / k
