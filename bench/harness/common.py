"""What every cell's run shares: finding the cell and its files by name,
the device check, the peak table, the compile cache, and the result line."""

from __future__ import annotations

import importlib.util
import json
import os
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]       # the checkout
BENCH = ROOT / "bench"


class Refusal(Exception):
    """The run cannot be made here; exit nonzero and print no result."""


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict:
    path = ROOT / "BENCHMARK.json"
    if not path.exists():
        raise Refusal(f"no {path}")
    return load_json(path)


def cell_plan(bm: Dict, name: str) -> Dict:
    """The cell ``name`` with its configuration, mix and metric entries."""
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise Refusal(f"unknown workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bm["configs"]}[cell["config"]]

    def mine(m):
        return "workloads" not in m or name in m["workloads"]

    return {
        "cell": cell,
        "config_entry": conf,
        "config": load_json(ROOT / conf["file"]),
        "mix": load_json(BENCH / "mixes" / f"{cell['traffic']}.json"),
        "end_to_end": [m for m in bm["end_to_end"] if mine(m)],
        "per_layer": [m for m in bm["per_layer"] if mine(m)],
        "limits": load_json(BENCH / "limits" / f"{name}.json"),
    }


def seed32(seed: int) -> int:
    """A 31-bit key for JAX from any non-negative ``--seed``."""
    import numpy as np
    state = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return int(state) & 0x7FFFFFFF


def device_record(jax, chips: int) -> Dict:
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise Refusal(f"no TPU: first device is {d.platform} "
                      f"({d.device_kind}); a CPU reading is no device metric")
    if len(devs) < chips:
        raise Refusal(f"cell needs {chips} chips, found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def peaks(kind: str) -> Dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise Refusal(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def enable_compile_cache(jax) -> str:
    """The program's persistent compile cache (``launch/cache.py``: its
    ``JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache`` at the root of the
    checkout), kept for every program, however quick to compile."""
    from repro.launch.cache import enable_compile_cache as program_cache
    where = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class CompileCount:
    """Programs compiled, or loaded from the compile cache, while ``on``:
    none should be inside a measured window."""

    EVENT = "/jax/compilation_cache/compile_requests_use_cache"

    def __init__(self):
        import jax
        self.n, self.on = 0, False
        jax.monitoring.register_event_listener(self._event)

    def _event(self, name, **_):
        if self.on and name == self.EVENT:
            self.n += 1


def memory_stats(devices) -> List[Dict]:
    return [d.memory_stats() or {} for d in devices]


def memory_peak(stats: List[Dict]) -> int:
    """The fullest chip's peak: its allocator's ``peak_bytes_in_use``, or,
    where more, the bytes in use when read plus ``peak_bytes_reserved``,
    the memory the TPU runtime holds for its programs' scratch (temp),
    which ``peak_bytes_in_use`` leaves out.  Read while the window's
    state is still alive."""
    def one(s):
        return max(int(s.get("peak_bytes_in_use", 0)),
                   int(s.get("bytes_in_use", 0))
                   + int(s.get("peak_bytes_reserved", 0)))
    log(f"bench: memory stats {stats[0] if stats else None}")
    return max([one(s) for s in stats] or [0])


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.exists():
        raise Refusal(f"missing {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(config: Dict):
    return load_module(BENCH / "drivers" / f"{config['driver']}.py",
                       f"bench_driver_{config['driver']}")


def reference(config: Dict):
    return importlib.import_module(f"bench.reference.{config['reference']}")


def read_layer_metrics(entries: List[Dict], inputs: Dict) -> Dict:
    """Each per-layer metric from its own reader ``bench/metrics/<name>.py``
    (``read(inputs) -> float | None``); a reader with nothing to read
    returns None and the metric is left out."""
    out = {}
    for m in entries:
        mod = load_module(BENCH / "metrics" / f"{m['name']}.py",
                          "bench_metric_" + m["name"].replace(".", "_"))
        v = mod.read(inputs)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def result(correct: bool, attempted: int, failed: int, metrics: Dict,
           device: Dict, compared: List[Dict],
           breakdown: Optional[Dict] = None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["compared"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                       for c in compared}
    return json.dumps(out)


def tmpdir(tag: str) -> str:
    import tempfile
    return tempfile.mkdtemp(prefix=f"bench_{tag}_",
                            dir=os.environ.get("TMPDIR") or None)
