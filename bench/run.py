#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are looked up
by name in ``BENCHMARK.json``; each sits in a file of its own under
``bench/`` (see ``bench/README.md``).  The run makes its weights and
inputs from ``--seed``, warms every shape it will use (set-up), measures
for ``--seconds``, checks what the timed path produced against the plain
reference, and prints one JSON object as its last line of standard
output.  With ``--trace 1`` the metrics are the cell's per-layer metrics,
read from a profiler trace of the window; with ``--trace 0`` they are its
end-to-end metrics.  Without a TPU, or with fewer chips than the cell
asks for, it exits nonzero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.harness import common  # noqa: E402


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, device_check=True, variant=None, **extra) -> int:
    """One run of one cell; returns the exit code.  The command line sets
    neither keyword: ``variant`` is set by ``control.py`` (the control,
    the program's int8 paths, a planted fault), ``sweep.py`` (``no_check``)
    and the tests under ``bench/tests``; ``device_check=False`` only by
    those tests (a CPU run at a tiny size).  ``extra`` goes to the driver
    as it is (``control.py``: a serving sample's size, a file for the
    served tokens' gaps)."""
    args = parse(argv)
    try:
        plan = common.cell_plan(common.benchmark(), args.workload)
        import jax
        if device_check:
            dev = common.device_record(jax, plan["cell"]["chips"])
        else:
            d = jax.devices()[0]
            dev = {"platform": d.platform, "kind": d.device_kind,
                   "count": plan["cell"]["chips"]}
        pk = common.peaks(dev["kind"])
    except common.Refusal as e:
        common.log(f"bench: refused: {e}")
        return 2
    common.log(f"bench: {args.workload} seed={args.seed} device={dev} "
               f"cache={common.enable_compile_cache(jax)}")
    drv = common.driver(plan["config"])
    out = drv.run(dict(args=args, plan=plan, peaks=pk, device=dev,
                       t_start=T_START, variant=variant, **extra))
    dev = dict(dev, memory_peak_bytes=int(out["memory_peak_bytes"]))
    if args.trace:
        dev.update(busy_s=out["busy_s"], window_s=out["window_s"])
        metrics = common.read_layer_metrics(plan["per_layer"], out["layer"])
    else:
        metrics = {m["name"]: {"value": float(out["e2e"][m["name"]]),
                               "unit": m["unit"]}
                   for m in plan["end_to_end"]}
    line = common.result(out["correct"], out["attempted"], out["failed"],
                         metrics, dev, out["compared"],
                         out.get("breakdown") if args.trace else None)
    for c in out["compared"]:
        common.log(f"compared {c['name']} = {c['value']!r} "
                   f"limit {c['limit']!r}")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
