"""collective_share.train-40l: the share of the traced window in which a
chip's core ran collectives and nothing else (``collective_s`` of
``harness/collectives.py``: the self time of all-reduce, all-gather,
reduce-scatter, all-to-all and collective-permute ops, each ``-done`` a
wait the compute did not hide), averaged over the chips."""


def read(x):
    t = x["trace"]
    if "collective_s" not in t or not t["window_s"]:
        return None
    return 100.0 * t["collective_s"] / t["window_s"]
