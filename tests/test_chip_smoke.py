"""CPU runs of the paths that run on the chip: the train and serve
launchers at smoke size on a 1x1 mesh, and ``chip_smoke.py``'s phase
functions at smoke size with the Pallas kernels in interpret mode.  The
script itself refuses a CPU."""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

import chip_smoke as cs
from repro.configs import get_smoke_config
from repro.distributed.context import clear_constraints
from repro.launch import serve as serve_launch
from repro.launch import train as train_launch

CFG = get_smoke_config("granite-3-2b")
ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture
def constraints_cleared():
    yield
    clear_constraints()


def test_chip_smoke_refuses_cpu(capsys):
    assert cs.main([]) == 2
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU" in out.err


def test_train_launcher_smoke(constraints_cleared):
    out = train_launch.run(train_launch.parse_args(
        ["--arch", "granite-3-2b", "--smoke", "--steps", "3",
         "--batch", "4", "--seq", "16"]))
    assert int(out["state"]["step"]) == 3
    assert out["skipped"] == 0 and out["rollbacks"] == 0


def test_serve_launcher_scheduler_paged_int4(constraints_cleared, capsys):
    serve_launch.run(serve_launch.parse_args(
        ["--arch", "granite-3-2b", "--smoke", "--scheduler", "--paged",
         "--kv-quant", "int4", "--weights", "rtn:int4", "--n-requests", "6",
         "--prompt-len", "12", "--new-tokens", "4"]))
    out = capsys.readouterr().out
    assert "outputs identical: True" in out
    # the launcher replays the workload twice (warm-up, then measured)
    assert "lifecycle counters: {'submitted': 12, 'admitted': 12, " \
        "'completed': 12}" in out


def test_chip_smoke_train_phases(constraints_cleared):
    check = cs.Checks(require_kernels=False)
    tcfg = cs.train_config(attn_chunk=8, logit_chunk=8)
    tr = cs.train_phase(CFG, check, batch=2, seq=16, steps=2, tcfg=tcfg)
    assert tr["opt_fused"] and len(tr["losses"]) == 2
    cmp_cfg = dataclasses.replace(CFG, n_layers=1)
    cmp = cs.opt_compare(cmp_cfg, check, batch=2, seq=16)
    assert cmp["update_rel_l2"] <= cs.OPT_TOL
    assert check.failed == []


def test_chip_smoke_serve_phases():
    check = cs.Checks(require_kernels=False)
    params = cs.serving_params(CFG)
    sv = cs.serve_phase(CFG, params, check, n_slots=2, chunk=8,
                        prompt_lens=(24, 16, 8), shared=8, new_tokens=4)
    assert sv["tokens"] == 12 and sv["prefill_tokens_skipped"] > 0
    lc = cs.logits_compare(CFG, params, check, batch=2, prompt_len=16,
                           block_size=8)
    assert lc["decode_rel_l2"] <= cs.LOGITS_TOL
    assert check.failed == []


def test_bench_runner_exits_nonzero_on_failure(monkeypatch, capsys):
    from benchmarks import bench_kernels, run

    def boom():
        raise RuntimeError("bench broke")

    monkeypatch.setattr(bench_kernels, "main", boom)
    assert run.main(["--only", "kernels"]) == 1
    assert "benches failed: kernels" in capsys.readouterr().err


def test_chip_smoke_checks_record_failures():
    check = cs.Checks()
    assert not check("x", False)
    assert not check.kernels("k", {"wqt_matmul": 1}, "decode_attn_paged")
    assert check.kernels("k2", {"opt_step": 2}, "opt_step")
    assert check.failed == ["x", "k"]


CACHED = textwrap.dedent("""
    import jax, jax.numpy as jnp
    from repro.launch.cache import enable_compile_cache
    print("DIR", enable_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(jnp.ones(5)))
""")


def test_compile_cache_is_written_where_the_env_says(tmp_path):
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    out = subprocess.run([sys.executable, "-c", CACHED], env=env,
                         cwd=tmp_path, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    assert f"DIR {tmp_path}" in out.stdout
    assert any(p.name.startswith("jit_") for p in tmp_path.iterdir())


def test_compile_cache_defaults_to_the_ignored_repo_dir(monkeypatch):
    from repro.launch import cache
    calls = []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(cache.jax.config, "update",
                        lambda *a: calls.append(a))
    want = os.path.realpath(os.path.join(ROOT, ".jax_cache"))
    assert cache.enable_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_state_bytes_per_device_counts_every_shard():
    x = jax.numpy.zeros((4, 8), np.float32)
    assert cs.state_bytes_per_device({"a": x, "b": x}) == {
        jax.devices()[0].id: 2 * 4 * 8 * 4}


SHARDED = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import chip_smoke as cs
    from repro.configs import get_smoke_config
    check = cs.Checks(require_kernels=False)
    cs.train_config = lambda f=cs.train_config: f(attn_chunk=8, logit_chunk=8)
    rec = cs.sharded_phase(get_smoke_config("granite-3-2b"), check,
                           batch=4, seq=16, steps=2)
    print("RESULT", check.failed, rec["loss_max_rel"])
""")


def test_chip_smoke_sharded_phase_on_four_cpu_devices():
    """The --chips 4 phase on 4 virtual CPU devices: the fused step runs
    per shard (shard_map), losses match one device, state is spread."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", SHARDED], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [x for x in out.stdout.splitlines() if x.startswith("RESULT")][0]
    assert line.startswith("RESULT []"), out.stdout[-3000:]
