#!/usr/bin/env python3
"""Readings for the limits of ``correct``: a cell's run on several seeds in
one process, as the program runs (``--variant program``); with the
control (``--variant control``: the reference with every matmul operand
rounded to float8 e4m3, one step below the configuration's bfloat16, in
the program's place; a serving cell logs the program's own reading of
the same tokens beside it); with the program's own int8-activation paths
(``int8_act`` for training, ``w4a8`` for serving); or with a fault
planted (``half_batch``: half of each training batch left out, the mean
taken over the rest).

    python3 bench/control.py --workload <cell> --variant control \
        --seeds 11,12,13 --seconds 20

Prints each run's compared numbers, then one JSON line with all of them.
The benchmark's own runs never run this; it is how the limits in
``bench/limits/`` were read (PERF.md, section 2).
"""

import argparse
import contextlib
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--variant", default="program",
                    choices=("program", "control", "int8_act", "w4a8",
                             "half_batch"))
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5)
    ap.add_argument("--sample", default="",
                    help="serving: requests,min_tokens of the sample "
                         "compared (default: the mix's)")
    ap.add_argument("--dump", default="",
                    help="serving: a directory for each seed's per-token "
                         "gaps (.npz)")
    a = ap.parse_args(argv)
    variant = None if a.variant == "program" else a.variant
    extra = {}
    if a.sample:
        n, m = (int(x) for x in a.sample.split(","))
        extra["sample"] = {"requests": n, "min_tokens": m}
    if a.dump:
        os.makedirs(a.dump, exist_ok=True)
    readings = []
    for seed in a.seeds.split(","):
        buf = io.StringIO()
        if a.dump:
            extra["dump"] = os.path.join(a.dump, f"{a.variant}_{seed}.npz")
        with contextlib.redirect_stdout(buf):
            rc = run.main(["--workload", a.workload, "--seed", seed,
                           "--seconds", str(a.seconds), "--trace", "0"],
                          variant=variant, **extra)
        if rc != 0:
            print(f"seed {seed}: exit {rc}", flush=True)
            return rc
        out = json.loads(buf.getvalue().strip().splitlines()[-1])
        vals = {k: v["value"] for k, v in out["compared"].items()}
        print(f"seed {seed} {a.variant}: {vals} correct={out['correct']} "
              f"metrics={ {k: v['value'] for k, v in out['metrics'].items()} }",
              flush=True)
        readings.append({"seed": int(seed), **vals})
    print(json.dumps({"workload": a.workload, "variant": a.variant,
                      "readings": readings}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
