"""Fused optimizer-step kernel + fused core tests: kernel vs the pure-jnp
oracle (masked at quantization-grid knife edges, same convention as
test_kernels.py), fused-core vs unfused-chain equivalence at clip=inf,
backend routing of the ``use_kernel`` auto-default, chain validation of
``applies_updates``, and sharding of the fused state."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import QuantConfig, QuantPolicy, get_format, quantize
from repro.data import lm_batch, permutation_table
from repro.kernels.opt_step import fused_opt_step_leaf, opt_step_ref
from repro.launch.mesh import make_mesh
from repro.models.lm import LMConfig, lm_init
from repro.optim import (adamw, adamw_core, chain, constant,
                         fused_lotion_adamw_core)
from repro.train import TrainConfig, init_state, make_optimizer, make_train_step

CFG = LMConfig(name="t", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
               d_ff=128, vocab=64, dtype=jnp.float32, remat=False)
POLICY = QuantPolicy(min_size=256)

HYP = dict(lr=1e-3, bc1=0.1, bc2=0.05, clip_scale=0.7, b1=0.9, b2=0.95,
           eps=1e-8, weight_decay=0.01)


def _batch(seed, step, b=8, l=32):
    perm = permutation_table(seed, CFG.vocab)
    return lm_batch(seed, step, b, l, CFG.vocab, perm)


def _rand4(shape, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    w = jax.random.normal(ks[0], shape) * 2.0
    g = jax.random.normal(ks[1], shape) * 0.1
    mu = jax.random.normal(ks[2], shape) * 0.01
    nu = jnp.abs(jax.random.normal(ks[3], shape)) * 0.01
    return w, g, mu, nu


def _grid_mask(w, fmt_name, bs, tol=1e-3):
    """True where w is safely AWAY from a quantization grid point — at
    grid points the Clarke subdifferential is set-valued and a 1-ulp
    difference in w/s flips which one-sided derivative the kernel
    returns (see the knife-edge note in tests/test_kernels.py)."""
    fmt = get_format(fmt_name)
    lo, hi = (quantize.rr_neighbors(w, fmt, bs) if bs == -1 else
              quantize.rr_neighbors(w.reshape(-1, bs), fmt, bs))
    lo = np.asarray(lo).reshape(-1)[: w.size].reshape(w.shape)
    hi = np.asarray(hi).reshape(-1)[: w.size].reshape(w.shape)
    wn = np.asarray(w)
    gap = np.maximum(hi - lo, 1e-9)
    d = np.minimum(np.abs(wn - lo), np.abs(hi - wn)) / gap
    nondegenerate = (hi - lo) > 1e-6 * (np.abs(wn) + 1.0)
    return (d > tol) & nondegenerate


@pytest.mark.parametrize("fmt", ["int4", "int8", "fp4"])
@pytest.mark.parametrize("bs", [-1, 128])
@pytest.mark.parametrize("shape", [(8, 256), (3, 5, 256), (64, 384)])
def test_opt_step_kernel_matches_ref(fmt, bs, shape):
    w, g, mu, nu = _rand4(shape, seed=1)
    lam = 3000.0
    got = fused_opt_step_leaf(w, g, mu, nu, lam=lam, fmt_name=fmt,
                              block_size=bs, **HYP)
    want = opt_step_ref(w, g, mu, nu, lam=lam, fmt_name=fmt,
                        block_size=bs, **HYP)
    mask = _grid_mask(w, fmt, bs)
    assert mask.mean() > 0.9
    for a, b, name in zip(got[:3], want[:3], ("w", "mu", "nu")):
        np.testing.assert_allclose(np.asarray(a)[mask], np.asarray(b)[mask],
                                   atol=1e-5, rtol=1e-4, err_msg=name)
    np.testing.assert_allclose(float(got[3]), float(want[3]),
                               rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("shape", [(8, 256), (130, 96)])
def test_opt_step_kernel_lam0_is_plain_adamw(shape):
    """lam=0 (non-eligible leaves): pure fused clip+AdamW, no grid math,
    no knife edges — tight comparison everywhere, zero penalty."""
    w, g, mu, nu = _rand4(shape, seed=2)
    got = fused_opt_step_leaf(w, g, mu, nu, lam=0.0, fmt_name="int4",
                              block_size=-1, **HYP)
    want = opt_step_ref(w, g, mu, nu, lam=0.0, fmt_name="int4",
                        block_size=-1, **HYP)
    for a, b in zip(got[:3], want[:3]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-6, rtol=1e-6)
    assert float(got[3]) == 0.0


def test_fused_core_matches_unfused_chain_single_update():
    """One fused update == the clip->lotion->adamw chain's update on the
    same state (clip=inf), leafwise at fp32 tolerance away from grid
    knife edges; penalty and gnorm metric scalars agree."""
    params = {"proj/wq": jax.random.normal(jax.random.PRNGKey(0), (64, 64)),
              "norm_scale": jax.random.normal(jax.random.PRNGKey(1), (64,))}
    grads = jax.tree.map(lambda x: x * 0.03, params)
    common = dict(fmt_name="int4", lam=500.0, block_size=-1, policy=POLICY)
    fused = fused_lotion_adamw_core(constant(1e-3), weight_decay=0.01,
                                    clip_norm=float("inf"), **common)
    from repro.optim import clip_global_norm, lotion_decoupled
    unfused = chain(clip_global_norm(float("inf")),
                    lotion_decoupled("int4", 500.0, -1, policy=POLICY),
                    adamw_core(constant(1e-3), weight_decay=0.01))

    st_f = fused.init(params)
    st_u = unfused.init(params)
    # a couple of updates so moments are non-zero and the penalty bites
    for i in range(3):
        g = jax.tree.map(lambda x: x * (1.0 + 0.1 * i), grads)
        new_p_f, st_f = fused.update(g, st_f, params)
        upd_u, st_u = unfused.update(g, st_u, params,
                                     fisher=unfused.fisher(st_u))
        new_p_u = jax.tree.map(lambda p, u: p + u, params, upd_u)
        flat_f = jax.tree_util.tree_flatten_with_path(new_p_f)[0]
        flat_u = jax.tree_util.tree_flatten_with_path(new_p_u)[0]
        for (path, a), (_, b) in zip(flat_f, flat_u):
            wv = params[path[0].key]
            if POLICY.eligible(path, wv):
                mask = _grid_mask(np.asarray(wv), "int4", -1)
            else:
                mask = np.ones(wv.shape, bool)
            np.testing.assert_allclose(np.asarray(a)[mask],
                                       np.asarray(b)[mask],
                                       atol=1e-6, rtol=1e-5)
        params = new_p_u
        st_f = {**st_f, "mu": st_u[-1]["mu"], "nu": st_u[-1]["nu"]}
        np.testing.assert_allclose(float(st_f["gnorm"]),
                                   float(st_u[0]["gnorm"]), rtol=1e-6)
        np.testing.assert_allclose(float(st_f["penalty"]),
                                   float(st_u[1]["penalty"]), rtol=1e-4)


def test_fused_train_step_runs_and_matches_metrics():
    """Full LM train step with the fused core: selected by make_optimizer
    (use_kernel=True), runs under jit, and tracks the unfused chain's
    loss/penalty/grad_norm closely over several steps."""
    params = lm_init(jax.random.PRNGKey(0), CFG)
    metrics = {}
    for use_kernel in (True, False):
        qc = QuantConfig(method="lotion", fmt_name="int4", lam=100.0,
                         policy=POLICY, use_kernel=use_kernel)
        tc = TrainConfig(quant=qc, clip_norm=float("inf"))
        tx = make_optimizer(tc, adamw(constant(1e-3)))
        assert tx.applies_updates == use_kernel
        step = jax.jit(make_train_step(CFG, tc, tx))
        st = init_state(params, tx)
        for s in range(3):
            st, m = step(st, _batch(0, s))
        metrics[use_kernel] = m
        if use_kernel:
            assert set(st["opt"]) == {"mu", "nu", "count", "penalty",
                                      "gnorm"}
            assert int(st["opt"]["count"]) == 3
    for key in ("loss", "ce", "penalty", "grad_norm"):
        np.testing.assert_allclose(float(metrics[True][key]),
                                   float(metrics[False][key]),
                                   rtol=1e-4, err_msg=key)
    assert float(metrics[True]["penalty"]) > 0.0


def test_loss_placement_with_fused_core_keeps_penalty_metric():
    """With penalty_placement='loss' the fused core runs lam=0 (the
    penalty lives in the loss); its state must NOT carry a zero
    'penalty' key that would clobber the real loss-aux penalty metric
    (regression: fused+loss reported penalty=0 while unfused reported
    the true value)."""
    params = lm_init(jax.random.PRNGKey(0), CFG)
    vals = {}
    for use_kernel in (True, False):
        qc = QuantConfig(method="lotion", fmt_name="int4", lam=100.0,
                         policy=POLICY, use_kernel=use_kernel,
                         penalty_placement="loss")
        tc = TrainConfig(quant=qc, clip_norm=float("inf"))
        tx = make_optimizer(tc, adamw(constant(1e-3)))
        if use_kernel:
            assert tx.applies_updates and "penalty" not in tx.init(params)
        step = jax.jit(make_train_step(CFG, tc, tx))
        st = init_state(params, tx)
        for s in range(2):      # step 2: Fisher (nu) non-zero -> penalty > 0
            st, m = step(st, _batch(0, s))
        vals[use_kernel] = (float(m["penalty"]), float(m["loss"]))
    assert vals[True][0] > 0.0
    np.testing.assert_allclose(vals[True][0], vals[False][0], rtol=1e-4)
    np.testing.assert_allclose(vals[True][1], vals[False][1], rtol=1e-5)


def test_use_kernel_default_routes_by_backend():
    """CPU default (use_kernel=None): jnp chain, no pallas_call anywhere
    in the step; explicit True forces the fused kernel core."""
    q = QuantConfig(method="lotion", lam=100.0, policy=POLICY)
    assert q.use_kernel is None
    assert q.kernel_enabled == (jax.default_backend() == "tpu")
    assert QuantConfig(use_kernel=True).kernel_enabled
    assert not QuantConfig(use_kernel=False).kernel_enabled

    if jax.default_backend() == "tpu":
        pytest.skip("default routing below is the CPU/GPU side")
    tc = TrainConfig(quant=q)
    tx = make_optimizer(tc, adamw(constant(1e-3)))
    assert not tx.applies_updates       # unfused chain selected
    params = lm_init(jax.random.PRNGKey(0), CFG)
    step = make_train_step(CFG, tc, tx)
    jaxpr = jax.make_jaxpr(step)(init_state(params, tx), _batch(0, 0))
    assert "pallas_call" not in str(jaxpr)


def test_fused_core_rejected_as_nonterminal_link():
    fused = fused_lotion_adamw_core(constant(1e-3), policy=POLICY)
    with pytest.raises(ValueError, match="LAST link"):
        chain(fused, adamw_core(constant(1e-3)))
    # terminal position is fine
    chain(adamw_core(constant(1e-3)), fused)


def test_fused_core_config_mismatch_rejected():
    def qcfg(**kw):
        base = dict(method="lotion", lam=100.0, policy=POLICY,
                    use_kernel=True)
        base.update(kw)
        return QuantConfig(**base)

    lotion_tc = TrainConfig(quant=qcfg())
    plain_fused = fused_lotion_adamw_core(constant(1e-3),
                                          clip_norm=lotion_tc.clip_norm,
                                          policy=POLICY)
    with pytest.raises(ValueError, match="lam=0"):
        make_optimizer(lotion_tc, plain_fused)
    lotion_fused = fused_lotion_adamw_core(constant(1e-3), lam=100.0,
                                           clip_norm=lotion_tc.clip_norm,
                                           policy=POLICY)
    with pytest.raises(ValueError, match="LOTION term"):
        make_optimizer(TrainConfig(clip_norm=lotion_tc.clip_norm),
                       lotion_fused)
    # baked-in values that disagree with the train config must raise,
    # not silently train with the core's versions
    with pytest.raises(ValueError, match="clip_norm"):
        make_optimizer(TrainConfig(quant=qcfg(), clip_norm=0.5),
                       lotion_fused)
    with pytest.raises(ValueError, match="use_kernel"):
        make_optimizer(TrainConfig(quant=qcfg(use_kernel=False)),
                       lotion_fused)
    with pytest.raises(ValueError, match="lam"):
        make_optimizer(TrainConfig(quant=qcfg(lam=7.0)), lotion_fused)
    with pytest.raises(ValueError, match="policy"):
        make_optimizer(TrainConfig(quant=qcfg(
            policy=QuantPolicy(min_size=512))), lotion_fused)
    with pytest.raises(ValueError, match="cannot be fused"):
        make_optimizer(TrainConfig(quant=qcfg(), ef_compress=True),
                       lotion_fused)
    # agreeing configs pass through
    assert make_optimizer(lotion_tc, lotion_fused) is lotion_fused


@pytest.mark.parametrize("momentum,fd,lam", [
    (0.0, None, 0.0), (0.9, None, 0.0), (0.9, 0.99, 500.0),
    (0.0, 0.95, 500.0)])
def test_fused_sgd_core_bitmatches_unfused_chain(momentum, fd, lam):
    """fused_lotion_sgd_core (jnp oracle path) is BIT-identical to the
    unfused clip -> [lotion] -> sgd_core chain over several updates —
    the SGD rule has no rounding-order freedom, so exact equality is the
    contract (ROADMAP PR 2 follow-up: fused SGD for the synthetic
    experiments)."""
    from repro.optim import clip_global_norm, fused_lotion_sgd_core, \
        lotion_decoupled, sgd_core
    params = {"proj/wq": jax.random.normal(jax.random.PRNGKey(0), (64, 64)),
              "norm_scale": jax.random.normal(jax.random.PRNGKey(1), (64,))}
    grads = jax.tree.map(lambda x: x * 0.03, params)
    fused = fused_lotion_sgd_core(constant(1e-2), momentum, fd, lam=lam,
                                  clip_norm=float("inf"), policy=POLICY,
                                  use_kernel=False)
    links = [clip_global_norm(float("inf"))]
    if lam:
        links.append(lotion_decoupled("int4", lam, -1, policy=POLICY))
    links.append(sgd_core(constant(1e-2), momentum=momentum,
                          fisher_decay=fd))
    unfused = chain(*links)
    st_f, st_u = fused.init(params), unfused.init(params)
    p_f = p_u = params
    for i in range(3):
        g = jax.tree.map(lambda x: x * (1.0 + 0.1 * i), grads)
        p_f, st_f = fused.update(g, st_f, p_f)
        upd, st_u = unfused.update(g, st_u, p_u,
                                   fisher=unfused.fisher(st_u))
        p_u = jax.tree.map(lambda p, u: p + u, p_u, upd)
        for a, b in zip(jax.tree.leaves(p_f), jax.tree.leaves(p_u)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert set(st_f) == ({"mu", "nu", "count", "gnorm", "penalty"}
                         if lam else {"mu", "nu", "count", "gnorm"})
    if fd is not None:
        np.testing.assert_array_equal(
            np.asarray(st_f["nu"]["proj/wq"]),
            np.asarray(unfused.fisher(st_u)["proj/wq"]))


@pytest.mark.parametrize("fmt,bs", [("int4", -1), ("int8", 128)])
def test_opt_step_kernel_sgd_matches_ref(fmt, bs):
    """The Pallas kernel's SGD core vs the jnp oracle, away from grid
    knife edges (same masking convention as the AdamW sweep)."""
    w, g, mu, nu = _rand4((8, 256), seed=5)
    kw = dict(lr=1e-2, bc1=1.0, bc2=1.0, clip_scale=0.7, lam=3000.0,
              fmt_name=fmt, block_size=bs, b1=0.0, b2=0.0, eps=0.0,
              weight_decay=0.0, core="sgd", momentum=0.9,
              fisher_decay=0.99)
    got = fused_opt_step_leaf(w, g, mu, nu, **kw)
    want = opt_step_ref(w, g, mu, nu, **kw)
    mask = _grid_mask(w, fmt, bs)
    assert mask.mean() > 0.9
    for a, b, name in zip(got[:3], want[:3], ("w", "mu", "nu")):
        np.testing.assert_allclose(np.asarray(a)[mask], np.asarray(b)[mask],
                                   atol=1e-5, rtol=1e-4, err_msg=name)
    np.testing.assert_allclose(float(got[3]), float(want[3]),
                               rtol=1e-4, atol=1e-7)


def test_make_optimizer_fuses_sgd_core():
    """use_kernel=True + sgd base -> the fused SGD core is selected;
    LOTION-on-SGD without fisher_decay falls back to the unfused chain
    (no Fisher estimate to fuse), matching the chain's own semantics."""
    from repro.optim import sgd
    q = QuantConfig(method="lotion", fmt_name="int4", lam=100.0,
                    policy=POLICY, use_kernel=True)
    tc = TrainConfig(quant=q, clip_norm=float("inf"))
    tx = make_optimizer(tc, sgd(constant(1e-2), momentum=0.9,
                                fisher_decay=0.99))
    assert tx.applies_updates and tx.tag == "fused_lotion_sgd"
    # no Fisher EMA -> unfused chain keeps LOTION semantics (fisher=None)
    tx2 = make_optimizer(tc, sgd(constant(1e-2), momentum=0.9))
    assert not tx2.applies_updates
    # without LOTION, plain SGD fuses regardless
    tx3 = make_optimizer(TrainConfig(quant=QuantConfig(use_kernel=True)),
                         sgd(constant(1e-2)))
    assert tx3.applies_updates and tx3.tag == "fused_lotion_sgd"


def test_fused_state_shardings_mirror_params():
    """Fused-core state: mu/nu inherit the parameter sharding (ZeRO
    posture), count/penalty/gnorm replicate — same rules as chain state."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import state_shardings

    mesh = make_mesh((1, 1), ("data", "model"))
    params = {"blk/wq": jnp.zeros((64, 128)), "norm_scale": jnp.zeros((64,))}
    fused = fused_lotion_adamw_core(constant(1e-3), lam=10.0, policy=POLICY)
    state_abs = jax.eval_shape(lambda: init_state(params, fused))
    sh = state_shardings(mesh, state_abs)
    assert sh["opt"]["mu"]["blk/wq"].spec == P("data", "model")
    assert sh["opt"]["nu"]["blk/wq"].spec == P("data", "model")
    assert sh["opt"]["mu"]["norm_scale"].spec == P()
    for scalar in ("count", "penalty", "gnorm"):
        assert sh["opt"][scalar].spec == P()
    assert sh["params"]["blk/wq"].spec == P("data", "model")


def test_fused_state_checkpoint_roundtrip(tmp_path):
    """Fused-core train state survives checkpoint save/restore bit-exactly
    (flat dict state — same pytree machinery as chain state)."""
    from repro import checkpoint as ckpt
    params = lm_init(jax.random.PRNGKey(0), CFG)
    qc = QuantConfig(method="lotion", lam=100.0, policy=POLICY,
                     use_kernel=True)
    tc = TrainConfig(quant=qc)
    tx = make_optimizer(tc, adamw(constant(1e-3)))
    step = jax.jit(make_train_step(CFG, tc, tx))
    st = init_state(params, tx)
    for s in range(2):
        st, _ = step(st, _batch(0, s))
    ckpt.save(str(tmp_path), 2, st)
    st2, s = ckpt.load(str(tmp_path), jax.eval_shape(lambda: st))
    assert s == 2
    for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(st2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
