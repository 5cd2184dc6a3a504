"""A tiny plan of each kind, for running the harness on the CPU."""

import copy
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]

TINY_MODEL = {"hidden_size": 64, "intermediate_size": 128,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 16, "vocab_size": 131, "compute_dtype": "float32",
              "attention_multiplier": 0.25}

# the cells whose harness these tests drive, by configuration and mix
CELLS = {
    "train.granite-3-2b.b8x2048": {
        "name": "train.granite-3-2b.b8x2048", "chips": 1, "traffic": "b8x2048",
        "config": "granite-3-2b-lotion-int4-train"},
    "serve.granite-3-2b.decode-heavy": {
        "name": "serve.granite-3-2b.decode-heavy", "chips": 1,
        "traffic": "decode-heavy", "config": "granite-3-2b-rtn-int4-serve"},
    "serve.granite-3-2b.prefill-heavy": {
        "name": "serve.granite-3-2b.prefill-heavy", "chips": 1,
        "traffic": "prefill-heavy", "config": "granite-3-2b-rtn-int4-serve"},
}

PEAKS = {"source": "test", "bf16_flops_per_s": 1e12,
         "int8_ops_per_s": 2e12, "hbm_bytes": 1e9,
         "hbm_bytes_per_s": 1e11, "ici_bits_per_s": 1e11}


def load(path):
    with open(BENCH / path) as f:
        return json.load(f)


def plan(cell: str):
    """The cell's plan from BENCHMARK.json, cut to a CPU size: two layers
    of width 64, short sequences, small batches, kernels off."""
    bm = load("../BENCHMARK.json")
    w = {x["name"]: x for x in bm["workloads"]}.get(cell) or CELLS[cell]
    conf = {"name": w["config"],
            "file": f"bench/configs/{w['config']}.json"}
    c = copy.deepcopy(load("../" + conf["file"]))
    c.update(TINY_MODEL, num_hidden_layers=2)
    mix = copy.deepcopy(load(f"mixes/{w['traffic']}.json"))
    if "train" in c:
        c["train"].update(use_kernel=False, attn_chunk=16, logit_chunk=16)
        mix.update(batch=4, seq=32)
    else:
        # wider than training's tiny model so that random logits spread
        # past the serving limit when a token is altered
        c.update(hidden_size=256, intermediate_size=512)
        c["serve"].update(use_kernel=False, block_size=16, prefill_chunk=16,
                          prefix_cache_blocks=8)
        mix.update(n_sizes=32, cache_len=96 if mix["loop"] == "closed"
                   else 112)
        mix["prompt"].update(min=8, max=48, median=16)
        mix["output"].update(min=4, max=40 if mix["loop"] == "closed"
                             else 12, median=8)
        if mix["loop"] == "closed":
            mix.update(clients=4, n_slots=4, warmup_steps=6)
        else:
            mix.update(n_slots=2, rate_per_s=20.0, warmup_requests=2)
        mix["sample"] = {"requests": 2, "min_tokens": 8}

    def mine(m):
        return "workloads" not in m or cell in m["workloads"]

    return {"cell": w, "config_entry": conf, "config": c, "mix": mix,
            "end_to_end": [m for m in bm["end_to_end"] if mine(m)],
            "per_layer": [m for m in bm["per_layer"] if mine(m)],
            "limits": load(f"limits/{cell}.json")}


def patch(monkeypatch, cell: str):
    from bench.harness import common
    p = plan(cell)
    monkeypatch.setattr(common, "cell_plan", lambda bm, name: p)
    monkeypatch.setattr(common, "peaks", lambda kind: PEAKS)
    monkeypatch.setattr(common, "enable_compile_cache", lambda jax: "off")
    return p
