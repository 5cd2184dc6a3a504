#!/usr/bin/env python3
"""Find the knee of an open-loop serving cell once: run its mix at several
offered rates in one process and print, per rate, the tail of time to
first token, the tokens completed per second, and how many requests of
the window still unanswered 20 s after it closed (a backlog that grows
with the window means the rate is past what the system sustains).

    python3 bench/sweep.py --workload serve.granite-3-2b.prefill-heavy \
        --rates 0.5,1,1.5,2 --seconds 30 --seed 5

The reference check is skipped; the cell's own runs keep it.  The rate
chosen from the sweep is written into the mix file by hand.
"""

import argparse
import contextlib
import copy
import io
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run  # noqa: E402
from bench.harness import common  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30)
    ap.add_argument("--seed", default="5")
    a = ap.parse_args(argv)
    real = common.cell_plan
    rows = []
    for rate in (float(r) for r in a.rates.split(",")):
        def plan(bm, name, rate=rate):
            p = copy.deepcopy(real(bm, name))
            p["mix"]["rate_per_s"] = rate
            p["mix"]["drain_s"] = 20
            p["end_to_end"] = p["end_to_end"] + [
                {"name": "serve_tokens_per_s", "unit": "tokens/s"},
                {"name": "completed_tokens_per_s", "unit": "tokens/s"}]
            return p
        common.cell_plan = plan
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = run.main(["--workload", a.workload, "--seed", a.seed,
                           "--seconds", str(a.seconds), "--trace", "0"],
                          variant="no_check")
        common.cell_plan = real
        if rc != 0:
            return rc
        out = json.loads(buf.getvalue().strip().splitlines()[-1])
        row = {"rate_per_s": rate, **{k: v["value"] for k, v in
                                      out["metrics"].items()},
               "sent": out["attempted"], "unanswered": out["failed"]}
        print(json.dumps(row), flush=True)
        rows.append(row)
    print(json.dumps({"workload": a.workload, "sweep": rows}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
