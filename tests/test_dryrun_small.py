"""Distribution tests: sharding rules + a REAL multi-device dry-run in a
subprocess (8 forced host devices; tests in this process keep 1 device).
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import cache_spec, data_batch_spec, param_spec

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


class _FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)

    @property
    def size(self):
        out = 1
        for v in self.shape.values():
            out *= v
        return out


MESH = _FakeMesh({"data": 16, "model": 16})
MESH3 = _FakeMesh({"pod": 2, "data": 16, "model": 16})


def _spec(path_strs, shape, **kw):
    class K:
        def __init__(self, k):
            self.key = k
    path = tuple(K(p) for p in path_strs)
    return param_spec(path, jax.ShapeDtypeStruct(shape, jax.numpy.float32), **kw)


def test_param_spec_rules():
    assert _spec(("stage", "b0_attn", "attn", "wq"), (13, 64, 128)) == \
        P(None, "data", "model")
    assert _spec(("stage", "b0_attn", "attn", "wo"), (13, 128, 64)) == \
        P(None, "model", "data")
    assert _spec(("stage", "b0_attn", "moe", "w_up"), (13, 8, 64, 128)) == \
        P(None, "model", "data", None)
    assert _spec(("embed",), (1000, 64)) == P("model", "data")
    assert _spec(("stage", "b0_attn", "pre_norm_scale"), (13, 64)) == P(None)
    assert _spec(("final_norm_scale",), (64,)) == P()
    # tp-only profile: no data sharding of weights
    assert _spec(("stage", "b0", "attn", "wq"), (13, 64, 128), fsdp=False) \
        == P(None, None, "model")


def test_batch_spec_divisibility():
    assert data_batch_spec(MESH, 256) == P(("data",))
    assert data_batch_spec(MESH3, 256) == P(("pod", "data"))
    assert data_batch_spec(MESH3, 1) == P(None)
    # batch 2: divisible by pod only
    assert data_batch_spec(MESH3, 2) == P(("pod",))


def test_cache_spec_rules():
    class K:
        def __init__(self, k):
            self.key = k

    def spec(path_strs, shape, mesh, batch):
        path = tuple(K(p) for p in path_strs)
        return cache_spec(path, jax.ShapeDtypeStruct(shape, jax.numpy.float32),
                          mesh, batch)

    # decode_32k: batch 128 shardable, len over model
    s = spec(("unit", "b0_attn", "k"), (32, 128, 32768, 8, 128), MESH, 128)
    assert s == P(None, ("data",), ("model",), None, None)
    # long_500k: batch 1 -> len over (data, model)
    s = spec(("unit", "b0_attn", "k"), (8, 1, 524288, 8, 256), MESH, 1)
    assert s == P(None, None, ("data", "model"), None, None)
    # quantized cache codes follow the same rule
    s = spec(("unit", "b0_attn", "k", "codes"), (32, 128, 32768, 8, 128),
             MESH, 128)
    assert s == P(None, ("data",), ("model",), None, None)
    # paged pool in the decode kernel's layout: blocks like a batch, the
    # block's tokens like a length, on codes (r, nb, bs, C) and scales
    # (r, nb, g, bs) alike
    s = spec(("unit", "b0_attn", "k", "codes"), (40, 512, 256, 256), MESH,
             512)
    assert s == P(None, ("data",), ("model",), None)
    s = spec(("unit", "b0_attn", "k", "scale"), (40, 512, 8, 256), MESH,
             512)
    assert s == P(None, ("data",), None, ("model",))
    s = spec(("unit", "b0_attn", "v", "scale"), (40, 273, 8, 256), MESH,
             273)
    assert s == P(None, None, None, ("data", "model"))
    # ssm state: heads over model
    s = spec(("unit", "b0_mamba", "ssm"), (9, 1, 80, 64, 64), MESH, 1)
    assert s == P(None, None, "model", None, None)


SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import repro.launch.dryrun as dr
    import repro.launch.mesh as mesh_mod

    def small_mesh(*, multi_pod=False):
        shape = (2, 2, 2) if multi_pod else (2, 4)
        axes = ("pod", "data", "model") if multi_pod else ("data", "model")
        return mesh_mod.make_mesh(shape, axes)

    dr.make_production_mesh = small_mesh
    import repro.launch.specs as sp
    from repro.configs import get_smoke_config
    sp.get_config = get_smoke_config
    import repro.configs as C
    C.SHAPES["t"] = dict(seq_len=64, global_batch=8, kind="train")
    C.SHAPES["d"] = dict(seq_len=64, global_batch=8, kind="decode")

    import json
    for mp in (False, True):
        for shape in ("t", "d"):
            rec, compiled = dr.lower_cell(
                "%ARCH%", shape, multi_pod=mp, n_microbatches=2,
                attn_chunk_train=32, logit_chunk=32)
            print("RESULT", json.dumps({
                "shape": shape, "mp": mp,
                "fits": rec["mem"]["fits_hbm"],
                "colls": sum(rec["collectives"]["per_op"].values())}))
""")


@pytest.mark.parametrize("arch", ["gemma2-2b", "dbrx-132b", "zamba2-2.7b"])
def test_dryrun_subprocess_small_mesh(arch):
    """End-to-end: lower+compile train & decode on real 8-device meshes
    (single- and multi-pod), with collectives present in the HLO."""
    code = SUBPROC.replace("%ARCH%", arch)
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    results = [json.loads(l.split("RESULT ")[1])
               for l in out.stdout.splitlines() if l.startswith("RESULT")]
    assert len(results) == 4
    assert all(r["fits"] for r in results)
    # a distributed program must actually communicate
    assert any(r["colls"] > 0 for r in results)
