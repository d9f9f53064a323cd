"""MoE sparse dispatch: the SELL combine path against the dense reference.

The combine step of token-choice MoE is an SpMM in disguise — these tests
pin the disguise down: the SELL execution (``ops.moe_dispatch`` /
``moe_forward(spec=dispatch="sell")`` / the service's coalesced
``moe_dispatch`` op) must match the dense one-hot einsum reference to
1e-10 across expert counts, top-k widths, capacity overflow, and the real
reduced MoE configs, and the routing-contract preflight must refuse
operands that are not routing matrices.
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs
from repro.analysis import LaunchPlanError
from repro.analysis.preflight import plan_moe_dispatch
from repro.kernels import ops
from repro.kernels.execspec import ExecSpec
from repro.models import model as M
from repro.models import moe as MOE
from repro.serve import GenerationConfig, ServeEngine
from repro.service import KernelRegistry, KernelService
from repro.sparse.formats import CSRMatrix, csr_to_sell_slabs

RNG = np.random.default_rng(11)

SELL = ExecSpec(dispatch="sell", vl=32)
DENSE = ExecSpec(dispatch="dense")
TOL = dict(rtol=1e-10, atol=1e-10)


def routing_csr(n_tok, n_slots, top_k, rng, dtype=np.float64) -> CSRMatrix:
    """Random routing matrix: <= top_k entries per row (some rows short —
    dropped assignments leave gaps in real routing too)."""
    indptr, indices, data = [0], [], []
    for _ in range(n_tok):
        w = int(rng.integers(0, top_k + 1))
        cols = np.sort(rng.choice(n_slots, size=w, replace=False))
        indices.extend(int(c) for c in cols)
        data.extend(rng.random(w).tolist())
        indptr.append(len(indices))
    return CSRMatrix(indptr=np.asarray(indptr, np.int64),
                     indices=np.asarray(indices, np.int32),
                     data=np.asarray(data, dtype), n_cols=n_slots)


# ---------------------------------------------------------------------------
# ops.moe_dispatch: SELL == dense on raw routing operands
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n_tok,n_slots,top_k,d", [
    (64, 96, 2, 16),       # mixtral-shaped top-2
    (33, 200, 4, 64),      # ragged token count, serving-tile d
    (128, 64, 6, 48),      # deepseek-shaped top-6, non-pow2 d
])
def test_ops_sell_matches_dense(n_tok, n_slots, top_k, d):
    csr = routing_csr(n_tok, n_slots, top_k, np.random.default_rng(n_tok))
    x = jnp.asarray(RNG.standard_normal((n_slots, d)))
    y_sell = np.asarray(ops.moe_dispatch(csr, x, spec=SELL, top_k=top_k))
    y_dense = np.asarray(ops.moe_dispatch(csr, x, spec=DENSE, top_k=top_k))
    assert y_sell.shape == (n_tok, d)
    np.testing.assert_allclose(y_sell, y_dense, **TOL)


def test_ops_rejects_routing_wider_than_topk():
    """A 16-wide row against top_k=2 fails launch preflight, not math."""
    csr = routing_csr(32, 64, 16, np.random.default_rng(3))
    x = jnp.asarray(RNG.standard_normal((64, 16)))
    with pytest.raises(LaunchPlanError, match="top_k"):
        ops.moe_dispatch(csr, x, spec=SELL, top_k=2)


def test_plan_moe_dispatch_rejects_non_routing_meta():
    """The routing contract: a general sparse matrix (bucket wider than
    pow2_ceil(top_k)) is not a dispatch operand, even though it would SpMM."""
    from repro.sparse.formats import random_csr

    from repro.analysis.preflight import SlabMeta

    wide = SlabMeta.from_slabs(
        csr_to_sell_slabs(random_csr(128, 128, 12.0, seed=2), c=32))
    plan = plan_moe_dispatch(wide, k=64, x_dtype="float64", top_k=2)
    assert not plan.ok
    assert any("top_k" in v for v in plan.violations)
    narrow = SlabMeta.from_slabs(csr_to_sell_slabs(
        routing_csr(128, 128, 2, np.random.default_rng(4)), c=32))
    assert plan_moe_dispatch(narrow, k=64, x_dtype="float64", top_k=2).ok


# ---------------------------------------------------------------------------
# moe_forward: full-layer agreement across configs
# ---------------------------------------------------------------------------


def _moe_cfg(n_experts, top_k, capacity_factor, n_shared=0):
    base = configs.reduced_config("mixtral-8x7b")
    return dataclasses.replace(base, moe=dataclasses.replace(
        base.moe, n_experts=n_experts, top_k=top_k,
        capacity_factor=capacity_factor, n_shared=n_shared))


def _forward_both(cfg, b=2, s=16, seed=0):
    params = MOE.init_moe_params(jax.random.PRNGKey(seed), cfg)
    x = jnp.asarray(
        np.random.default_rng(seed).standard_normal((b, s, cfg.d_model)))
    out_d, aux_d = MOE.moe_forward(params, cfg, x, spec=DENSE)
    out_s, aux_s = MOE.moe_forward(params, cfg, x, spec=SELL)
    return out_d, aux_d, out_s, aux_s


@pytest.mark.parametrize("name", ["mixtral-8x7b", "deepseek-moe-16b"])
def test_moe_forward_sell_matches_dense_reduced_configs(name):
    cfg = configs.reduced_config(name)
    out_d, aux_d, out_s, aux_s = _forward_both(cfg)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_d), **TOL)
    np.testing.assert_allclose(float(aux_s), float(aux_d), **TOL)


@pytest.mark.parametrize("e,k", [(4, 1), (8, 3), (16, 4)])
def test_moe_forward_sell_matches_dense_expert_sweep(e, k):
    cfg = _moe_cfg(e, k, capacity_factor=float(e))   # no drops
    out_d, aux_d, out_s, aux_s = _forward_both(cfg, seed=e * 10 + k)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_d), **TOL)
    np.testing.assert_allclose(float(aux_s), float(aux_d), **TOL)


def test_moe_forward_sell_matches_dense_under_capacity_overflow():
    """capacity_factor < 1 forces drops; both paths must drop the SAME
    tokens (and differ from the no-drop run, proving overflow engaged)."""
    tight = _moe_cfg(4, 2, capacity_factor=0.5)
    out_d, aux_d, out_s, aux_s = _forward_both(tight, b=2, s=32, seed=7)
    np.testing.assert_allclose(np.asarray(out_s), np.asarray(out_d), **TOL)
    np.testing.assert_allclose(float(aux_s), float(aux_d), **TOL)
    roomy = _moe_cfg(4, 2, capacity_factor=4.0)
    out_full, _, _, _ = _forward_both(roomy, b=2, s=32, seed=7)
    assert np.abs(np.asarray(out_full) - np.asarray(out_d)).max() > 1e-6


def test_moe_forward_auto_falls_back_dense_under_jit():
    """dispatch='auto' must keep moe_forward jittable: the tracer cannot
    host-pack SELL operands, so auto silently runs the dense path there —
    with output identical to the eager dense reference."""
    cfg = configs.reduced_config("mixtral-8x7b")
    params = MOE.init_moe_params(jax.random.PRNGKey(1), cfg)
    x = jnp.asarray(RNG.standard_normal((1, 8, cfg.d_model)))
    auto = ExecSpec(dispatch="auto", vl=32)
    jit_out, jit_aux = jax.jit(
        lambda p, xx: MOE.moe_forward(p, cfg, xx, spec=auto))(params, x)
    ref_out, ref_aux = MOE.moe_forward(params, cfg, x, spec=DENSE)
    np.testing.assert_allclose(np.asarray(jit_out), np.asarray(ref_out), **TOL)
    # the aux loss is computed in float32 (models/moe.py), and jit and eager
    # fuse its mean differently: they may differ by a few float32 ulps
    np.testing.assert_allclose(float(jit_aux), float(ref_aux),
                               rtol=1e-6, atol=1e-7)


def test_moe_forward_forced_sell_under_jit_raises():
    cfg = configs.reduced_config("mixtral-8x7b")
    params = MOE.init_moe_params(jax.random.PRNGKey(1), cfg)
    x = jnp.asarray(RNG.standard_normal((1, 8, cfg.d_model)))
    with pytest.raises(ValueError, match="concrete activations"):
        jax.jit(lambda p, xx: MOE.moe_forward(
            p, cfg, xx, spec=SELL))(params, x)


# ---------------------------------------------------------------------------
# service: register_moe envelope + coalesced moe_dispatch launches
# ---------------------------------------------------------------------------


def _moe_service(n_tokens=64, n_slots=96, d_model=16, top_k=2, **kw):
    reg = KernelRegistry()
    reg.register_moe("moe", n_tokens=n_tokens, n_slots=n_slots,
                     d_model=d_model, top_k=top_k)
    return KernelService(reg, n_slots=4, **kw)


def _payload(csr, x):
    return {"indptr": csr.indptr, "indices": csr.indices,
            "data": csr.data, "x": x}


def test_service_coalesces_moe_dispatch_requests():
    """Two engines' per-step routing in the same round = ONE block-diagonal
    SELL launch, each caller getting exactly its own rows back."""
    svc = _moe_service()
    rng = np.random.default_rng(5)
    reqs = []
    for i in range(3):
        csr = routing_csr(16 + 4 * i, 32, 2, rng)
        x = rng.standard_normal((32, 16))
        rid = svc.submit("moe_dispatch", "moe", _payload(csr, x))
        reqs.append((rid, csr, x))
    svc.drain()
    assert svc.stats["moe_dispatch_launches"] == 1
    assert svc.stats["served"] == 3
    for rid, csr, x in reqs:
        ref = np.asarray(ops.moe_dispatch(csr, jnp.asarray(x),
                                          spec=DENSE, top_k=2))
        np.testing.assert_allclose(svc.poll(rid), ref, **TOL)
    assert "latency_us_class_moe_dispatch" in svc.metrics
    assert svc.metrics.get("latency_us_class_moe_dispatch").count == 3


def test_service_validates_moe_payload_against_envelope():
    """Bad payloads fail their own request with a telling message and spare
    coalesced groupmates — the envelope registered is the contract."""
    svc = _moe_service(d_model=16, top_k=2, n_tokens=64)
    rng = np.random.default_rng(6)
    ok_csr = routing_csr(16, 32, 2, rng)
    ok_x = rng.standard_normal((32, 16))
    wide = routing_csr(16, 32, 5, rng)                  # rows wider than top_k
    while np.diff(wide.indptr).max() <= 2:              # ensure a wide row
        wide = routing_csr(16, 32, 5, rng)
    bad_width = svc.submit("moe_dispatch", "moe", _payload(wide, ok_x))
    bad_x = svc.submit("moe_dispatch", "moe",
                       _payload(ok_csr, rng.standard_normal((32, 7))))
    oob = routing_csr(16, 32, 2, rng)
    oob.indices[0] = 99                                 # column beyond x rows
    bad_col = svc.submit("moe_dispatch", "moe", _payload(oob, ok_x))
    good = svc.submit("moe_dispatch", "moe", _payload(ok_csr, ok_x))
    svc.drain()
    with pytest.raises(RuntimeError, match="top_k"):
        svc.poll(bad_width)
    with pytest.raises(RuntimeError, match="must have shape"):
        svc.poll(bad_x)
    with pytest.raises(RuntimeError, match="out of range"):
        svc.poll(bad_col)
    ref = np.asarray(ops.moe_dispatch(ok_csr, jnp.asarray(ok_x),
                                      spec=DENSE, top_k=2))
    np.testing.assert_allclose(svc.poll(good), ref, **TOL)
    assert svc.stats["failed"] == 3 and svc.stats["served"] == 1


def test_register_moe_rejects_bad_envelope():
    reg = KernelRegistry()
    with pytest.raises(ValueError, match="top_k"):
        reg.register_moe("moe", n_tokens=64, n_slots=96, d_model=16, top_k=0)
    op = reg.register_moe("moe", n_tokens=64, n_slots=96,
                          d_model=16, top_k=2)
    assert op.kind == "moe" and op.plans["moe_dispatch"].ok
    svc = KernelService(reg, n_slots=2)
    rng = np.random.default_rng(8)
    too_many = routing_csr(128, 32, 2, rng)             # rows beyond envelope
    rid = svc.submit("moe_dispatch", "moe",
                     _payload(too_many, rng.standard_normal((32, 16))))
    svc.drain()
    with pytest.raises(RuntimeError, match="envelope"):
        svc.poll(rid)


# ---------------------------------------------------------------------------
# fused serving: ServeEngine routing MoE combines through the service
# ---------------------------------------------------------------------------


def test_fused_generate_matches_plain_engine():
    """The whole point of the fusion: identical tokens, MoE launches
    counted on the shared loop, per-class latency split recorded."""
    cfg = configs.reduced_config("mixtral-8x7b")
    params = M.init_params(jax.random.PRNGKey(2), cfg)
    gcfg = GenerationConfig(max_new_tokens=4, cache_len=64)
    prompts = np.random.default_rng(9).integers(
        0, cfg.vocab_size, (2, 6)).astype(np.int32)

    plain = ServeEngine(cfg, params, gcfg).generate(prompts)

    reg = KernelRegistry()
    cap = int(6 * cfg.moe.top_k / cfg.moe.n_experts
              * cfg.moe.capacity_factor) + 1
    reg.register_moe("moe", n_tokens=2 * 6,
                     n_slots=2 * cfg.moe.n_experts * cap,
                     d_model=cfg.d_model, top_k=cfg.moe.top_k)
    svc = KernelService(reg, n_slots=4)
    eng = ServeEngine(cfg, params, gcfg, kernel_service=svc,
                      moe_operand="moe")
    assert eng.fused
    fused = eng.generate(prompts)

    np.testing.assert_array_equal(fused, plain)
    # one combine per MoE layer per step (prefill + 3 decode steps)
    assert svc.stats["moe_dispatch_launches"] == \
        cfg.n_layers * gcfg.max_new_tokens
    # one observation per generation step (prefill+sample, then decodes)
    assert "latency_us_class_lm_token" in svc.metrics
    assert svc.metrics.get("latency_us_class_lm_token").count == \
        gcfg.max_new_tokens
    assert svc.metrics.get("latency_us_class_moe_dispatch").count == \
        svc.stats["moe_dispatch_launches"]
