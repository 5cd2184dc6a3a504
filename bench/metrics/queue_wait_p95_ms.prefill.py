"""queue_wait_p95_ms.prefill: 95th percentile of the wait between a
request's due time and its admission to a slot (``Request.t_admit``, which
the scheduler stamps with the wall clock the benchmark passes as ``now``),
over the requests whose first token came in the traced window."""

import numpy as np


def read(x):
    w = x.get("queue_waits_ms") or []
    if not w:
        return None
    return float(np.percentile(np.asarray(w, np.float64), 95))
