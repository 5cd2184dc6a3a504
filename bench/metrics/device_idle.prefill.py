"""device_idle.prefill: the share of the traced window in which no program
ran on the device (1 - union of program executions / window), averaged
over the chips; the idle gaps are attributed to host spans in the
breakdown."""


def read(x):
    t = x["trace"]
    if not t["window_s"]:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
