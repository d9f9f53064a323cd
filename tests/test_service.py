"""Serving-subsystem tests: TuneCache, KernelRegistry, KernelService.

The load-bearing guarantees: (1) registering an operand whose signature the
persistent TuneCache has seen performs ZERO pad-factor measurements (the
pay-once tune contract, counted by monkeypatching
``repro.core.autotune.measured_pad_factor``); (2) the LM batcher and the
kernel service run the same admission loop (one batching core); (3) every
kernel served through the engine matches its host reference; (4) the
``ops.spmv`` repack-on-mismatch path reuses the recorded layout instead of
repacking twice; (5) schema-version mismatches in the cache raise a clear
error, never a KeyError.
"""
import json

import numpy as np
import pytest

import repro.core.autotune as autotune
from repro.core.jsonstore import SchemaVersionError
from repro.graphs import gen as G
from repro.kernels import ops
from repro.serve.batcher import Batcher
from repro.serve.slots import SlotLoop
from repro.service import (
    KernelRegistry,
    KernelService,
    TuneCache,
    operand_signature,
)
from repro.service.tunecache import SCHEMA_VERSION
from repro.sparse import formats as F

RNG = np.random.default_rng(7)


@pytest.fixture
def count_measures(monkeypatch):
    """Counter of measured_pad_factor calls (the expensive tune step)."""
    calls = {"n": 0}
    real = autotune.measured_pad_factor

    def counting(*args, **kwargs):
        calls["n"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(autotune, "measured_pad_factor", counting)
    return calls


@pytest.fixture
def small_world():
    csr = F.random_csr(200, 200, 6.0, seed=0, skew=1.0)
    graph = G.random_graph(n_nodes=128, avg_degree=5, seed=1)
    return csr, graph


def make_registry(csr, graph, cache=None):
    reg = KernelRegistry(cache=cache)
    reg.register_matrix("mat", csr)
    reg.register_graph("graph", graph)
    reg.register_fft("fft", 128)
    return reg


# ---------------------------------------------------------------------------
# Operand signatures
# ---------------------------------------------------------------------------


def test_signature_is_content_addressed():
    a = F.random_csr(60, 60, 4.0, seed=3)
    b = F.random_csr(60, 60, 4.0, seed=3)     # identical content
    c = F.random_csr(60, 60, 4.0, seed=4)     # same shape, other content
    assert operand_signature(a) == operand_signature(b)
    assert operand_signature(a) != operand_signature(c)
    assert operand_signature(a).key.startswith("csr:60x60:")
    # format changes the fingerprint kind, graphs are supported too
    assert operand_signature(F.csr_to_ellpack(a, c=16)).kind == "ellpack"
    g = G.random_graph(n_nodes=32, avg_degree=3, seed=0)
    assert operand_signature(g).kind == "graph"
    with pytest.raises(TypeError, match="unsupported operand"):
        operand_signature(np.zeros(3))


# ---------------------------------------------------------------------------
# TuneCache: persistence, warm hits, schema gate
# ---------------------------------------------------------------------------


def test_tunecache_roundtrip_and_zero_measures_on_hit(
        tmp_path, small_world, count_measures):
    csr, _ = small_world
    path = str(tmp_path / "tune.json")

    cold = TuneCache(path)
    reg = KernelRegistry(cache=cold)
    op1 = reg.register_matrix("m", csr)
    assert not op1.tune_was_cached
    cold_measures = count_measures["n"]
    assert cold_measures > 0
    cold.save()

    # fresh process simulation: reload from disk, re-register same content
    count_measures["n"] = 0
    warm = TuneCache(path)
    assert len(warm) == 1
    reg2 = KernelRegistry(cache=warm)
    op2 = reg2.register_matrix("same-content-other-name", csr)
    assert count_measures["n"] == 0            # the acceptance criterion
    assert op2.tune_was_cached
    assert (op2.tuned.c, op2.tuned.sigma, op2.tuned.w_block) == \
           (op1.tuned.c, op1.tuned.sigma, op1.tuned.w_block)
    assert op2.tuned.table == op1.tuned.table  # full table round-trips


def test_tunecache_same_process_second_registration_is_free(
        small_world, count_measures):
    csr, _ = small_world
    reg = KernelRegistry(cache=TuneCache())    # in-memory cache
    reg.register_matrix("a", csr)
    count_measures["n"] = 0
    op = reg.register_matrix("b", csr)         # same signature, new name
    assert count_measures["n"] == 0 and op.tune_was_cached
    # packed slabs were memoized as well: both names share the layout object
    assert reg.get("a").slabs is reg.get("b").slabs


def test_tunecache_future_schema_version_raises_clearly(tmp_path):
    path = tmp_path / "tune.json"
    path.write_text(json.dumps(
        {"schema_version": SCHEMA_VERSION + 1, "entries": {"ghost": {}}}))
    with pytest.raises(SchemaVersionError, match=(
            f"schema_version {SCHEMA_VERSION + 1}.*supports {SCHEMA_VERSION}"
            ".*newer version")):
        TuneCache(str(path))
    # non-strict mode degrades to the SweepStore behavior: warn + fresh
    with pytest.warns(RuntimeWarning, match="ignoring the stale store"):
        cache = TuneCache(str(path), strict=False)
    assert len(cache) == 0


def test_tunecache_save_requires_path():
    with pytest.raises(ValueError, match="without a path"):
        TuneCache().save()


def test_tunecache_nonstrict_save_replaces_stale_schema(tmp_path):
    """A non-strict cache that warned-and-ignored a stale store at load
    time must be able to replace it at save time — merge-on-save honors
    the instance's strict mode instead of wedging on the same document."""
    path = tmp_path / "tune.json"
    path.write_text(json.dumps(
        {"schema_version": SCHEMA_VERSION + 1, "entries": {"ghost": {}}}))
    with pytest.warns(RuntimeWarning, match="ignoring the stale store"):
        cache = TuneCache(str(path), strict=False)
    with pytest.warns(RuntimeWarning, match="ignoring the stale store"):
        cache.save()                           # replaces, never raises
    fresh = TuneCache(str(path))               # strict load now succeeds
    assert len(fresh) == 0


def test_tunecache_key_distinguishes_machines(small_world, count_measures):
    """The same operand tuned for two machines must occupy two cache
    entries — a hit may never return a layout scored for another machine."""
    from repro.core.campaign import hbm_like_machine, sve_like_machine

    csr, _ = small_world
    cache = TuneCache()
    reg_a = KernelRegistry(cache=cache, machine=hbm_like_machine())
    reg_b = KernelRegistry(cache=cache, machine=sve_like_machine())
    op_a = reg_a.register_matrix("m", csr)
    count_measures["n"] = 0
    op_b = reg_b.register_matrix("m", csr)
    assert count_measures["n"] > 0             # different machine re-tunes
    assert not op_b.tune_was_cached
    assert len(cache) == 2
    # the tuner honors the ISA cap: an sve-like machine never gets C > 8
    assert op_b.tuned.c <= sve_like_machine().max_vl
    assert op_a.tuned.c >= op_b.tuned.c


def test_packed_memo_is_lru_bounded():
    cache = TuneCache(max_packed=2)
    cache.packed_put(("a",), 1)
    cache.packed_put(("b",), 2)
    assert cache.packed_get(("a",)) == 1       # refresh "a"
    cache.packed_put(("c",), 3)                # evicts "b" (least recent)
    assert cache.packed_get(("b",)) is None
    assert cache.packed_get(("a",)) == 1 and cache.packed_get(("c",)) == 3
    assert cache.stats["packed"] == 2


def test_campaign_hints_narrow_the_tune_sweep(
        tmp_path, small_world, count_measures):
    """warm_from_sweeps is consumed, not just stored: a hinted registry
    measures strictly fewer pad factors than the cold full sweep."""
    from repro.core.campaign import SweepStore, run_campaign

    csr, _ = small_world
    KernelRegistry(cache=TuneCache()).register_matrix("m", csr)
    full_sweep = count_measures["n"]

    store = SweepStore(str(tmp_path / "sweeps.json"))
    store.put(run_campaign("machine-compare"))
    store.save()
    cache = TuneCache()
    cache.warm_from_sweeps(store.path)
    count_measures["n"] = 0
    op = KernelRegistry(cache=cache).register_matrix("m", csr)
    assert 0 < count_measures["n"] < full_sweep
    # the winner comes from the campaign-narrowed candidate list
    assert op.tuned.c in cache.candidate_vls_for("spmv", "tpu-v5e")

    # an operand with a FULL-grid entry is never re-measured just because
    # hints appeared afterwards: the hinted miss falls back to the full key
    cache_full = TuneCache()
    KernelRegistry(cache=cache_full).register_matrix("m", csr)  # full sweep
    cache_full.warm_from_sweeps(store.path)
    count_measures["n"] = 0
    op2 = KernelRegistry(cache=cache_full).register_matrix("m2", csr)
    assert count_measures["n"] == 0 and op2.tune_was_cached

    # and a missing store path fails loudly instead of seeding nothing
    with pytest.raises(FileNotFoundError, match="no campaign store"):
        TuneCache().warm_from_sweeps(str(tmp_path / "typo.json"))


def test_warm_from_sweeps_seeds_campaign_hints(tmp_path):
    from repro.core.campaign import SweepStore, run_campaign

    store = SweepStore(str(tmp_path / "sweeps.json"))
    store.put(run_campaign("machine-compare"))
    store.save()

    cache = TuneCache()
    seeded = cache.warm_from_sweeps(store.path)
    res = store.get("machine-compare")
    assert seeded == len(res.spec.machines) * len(res.spec.kernels)
    # the hint is a vector VL from the campaign grid, per (kernel, machine)
    for m in res.spec.machines:
        for kernel in res.spec.kernels:
            hint = cache.hint_vl(kernel, m.name)
            assert hint in res.spec.vls and hint != 0
    # hints narrow the candidate list around the campaign's verdict
    cands = cache.candidate_vls_for("spmv", "hbm-like")
    assert cache.hint_vl("spmv", "hbm-like") in cands
    assert cache.candidate_vls_for("spmv", "no-such-machine") is None


# ---------------------------------------------------------------------------
# One batching core
# ---------------------------------------------------------------------------


def test_lm_batcher_and_kernel_service_share_the_slot_loop():
    assert issubclass(Batcher, SlotLoop)
    assert issubclass(KernelService, SlotLoop)
    # the admission loop is inherited, not copy-pasted
    for method in ("run", "step", "_fill_slots", "_evict_done"):
        assert method not in Batcher.__dict__
        assert method not in KernelService.__dict__
        assert method in SlotLoop.__dict__


def test_slot_loop_rejects_zero_slots():
    with pytest.raises(ValueError, match="n_slots"):
        KernelService.__mro__[1].__init__(object.__new__(KernelService), 0)


# ---------------------------------------------------------------------------
# KernelService: correctness, coalescing, async API
# ---------------------------------------------------------------------------


def test_service_results_match_references(small_world):
    csr, graph = small_world
    svc = KernelService(make_registry(csr, graph), n_slots=4)

    x = RNG.standard_normal(csr.n_cols)
    sig = RNG.standard_normal((2, 128))
    r_spmv = svc.submit("spmv", "mat", x)
    r_bfs = svc.submit("bfs", "graph", source=3)
    r_pr = svc.submit("pagerank", "graph", iters=4)
    r_fft = svc.submit("fft", "fft", sig)
    assert svc.poll(r_spmv) is None            # async: nothing ran yet
    svc.drain()

    np.testing.assert_allclose(
        svc.poll(r_spmv), csr.matvec(x), rtol=1e-10, atol=1e-10)
    np.testing.assert_array_equal(
        svc.poll(r_bfs), G.bfs_reference(graph, 3))
    np.testing.assert_allclose(
        svc.poll(r_pr), G.pagerank_reference(graph, iters=4), rtol=1e-8)
    re, im = svc.poll(r_fft)
    want = np.fft.fft(sig, axis=-1)
    np.testing.assert_allclose(re, want.real, atol=1e-8)
    np.testing.assert_allclose(im, want.imag, atol=1e-8)
    assert svc.stats["served"] == 4 and svc.stats["failed"] == 0


def test_service_coalesces_fft_requests(small_world, monkeypatch):
    """Concurrent FFT requests against one plan become ONE kernel call."""
    from repro.kernels import fft as fft_k

    csr, graph = small_world
    svc = KernelService(make_registry(csr, graph), n_slots=8)
    calls = {"n": 0}
    real = fft_k.fft_stockham

    def counting(*args, **kwargs):
        calls["n"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(fft_k, "fft_stockham", counting)
    sigs = [RNG.standard_normal((1, 128)) for _ in range(5)]
    rids = [svc.submit("fft", "fft", s) for s in sigs]
    svc.drain()
    assert calls["n"] == 1                     # 5 requests, one launch
    assert svc.stats["coalesced"] >= 5 and svc.stats["max_group"] == 5
    for rid, s in zip(rids, sigs):
        re, _ = svc.poll(rid)
        np.testing.assert_allclose(re, np.fft.fft(s, axis=-1).real, atol=1e-8)


def test_service_more_requests_than_slots_all_complete(small_world):
    csr, graph = small_world
    svc = KernelService(make_registry(csr, graph), n_slots=2)
    xs = [RNG.standard_normal(csr.n_cols) for _ in range(7)]
    rids = [svc.submit("spmv", "mat", x) for x in xs]
    done = svc.drain()
    assert len(done) == 7
    for rid, x in zip(rids, xs):
        np.testing.assert_allclose(
            svc.poll(rid), csr.matvec(x), rtol=1e-10, atol=1e-10)


def test_service_errors_travel_to_the_caller(small_world):
    csr, graph = small_world
    svc = KernelService(make_registry(csr, graph), n_slots=2)
    with pytest.raises(ValueError, match="unknown op"):
        svc.submit("matmul", "mat", None)
    with pytest.raises(KeyError, match="not registered"):
        svc.submit("spmv", "nope", None)
    # one malformed request must not fail its coalesced groupmates: the bad
    # and good FFT land in the same (op, operand) group in the same round
    good_sig = RNG.standard_normal((1, 128))
    bad = svc.submit("fft", "fft", RNG.standard_normal((1, 64)))  # wrong len
    good = svc.submit("fft", "fft", good_sig)
    svc.drain()
    with pytest.raises(RuntimeError, match="signal length 64"):
        svc.poll(bad)
    re, _ = svc.poll(good)
    np.testing.assert_allclose(re, np.fft.fft(good_sig, axis=-1).real,
                               atol=1e-8)
    assert svc.stats["failed"] == 1 and svc.stats["served"] >= 1


def test_service_bad_request_spares_coalesced_groupmates(small_world):
    """A malformed payload fails its own request only — the valid request
    coalesced into the same (op, operand) group still completes."""
    csr, graph = small_world
    svc = KernelService(make_registry(csr, graph), n_slots=4)
    x = RNG.standard_normal(csr.n_cols)
    bad = svc.submit("spmv", "mat", None)              # malformed payload
    good = svc.submit("spmv", "mat", x)
    svc.drain()
    with pytest.raises(RuntimeError, match="failed"):
        svc.poll(bad)
    np.testing.assert_allclose(
        svc.poll(good), csr.matvec(x), rtol=1e-10, atol=1e-10)
    assert svc.stats["failed"] == 1 and svc.stats["served"] == 1


def test_service_validates_spmv_and_bfs_payloads(small_world):
    """Wrong-sized x / out-of-range source must error, not return garbage
    (JAX clamps out-of-bounds gathers, so silent success is the trap)."""
    csr, graph = small_world
    svc = KernelService(make_registry(csr, graph), n_slots=4)
    ok_x = RNG.standard_normal(csr.n_cols)
    bad_x = svc.submit("spmv", "mat", RNG.standard_normal(csr.n_cols - 7))
    ok = svc.submit("spmv", "mat", ok_x)
    bad_src = svc.submit("bfs", "graph", source=graph.n_nodes + 1)
    svc.drain()
    with pytest.raises(RuntimeError, match="must have shape"):
        svc.poll(bad_x)
    with pytest.raises(RuntimeError, match="out of range"):
        svc.poll(bad_src)
    np.testing.assert_allclose(
        svc.poll(ok), csr.matvec(ok_x), rtol=1e-10, atol=1e-10)


def test_service_release_of_done_request_still_in_slot(small_world):
    """Releasing after execute but before the next eviction round must not
    let _evict_done resurrect the request into `completed`."""
    csr, graph = small_world
    svc = KernelService(make_registry(csr, graph), n_slots=2)
    rid = svc.submit("spmv", "mat", RNG.standard_normal(csr.n_cols))
    assert svc.step()                          # admitted + executed
    assert svc.poll(rid) is not None           # done, but still in its slot
    svc.release(rid)
    assert all(s is None for s in svc.slots)
    assert not svc.step()                      # idle; nothing resurrected
    assert not svc.completed and svc.stats["served"] == 1


def test_service_ragged_fft_payload_spares_groupmates(small_world):
    csr, graph = small_world
    svc = KernelService(make_registry(csr, graph), n_slots=4)
    good_sig = RNG.standard_normal((1, 128))
    bad = svc.submit("fft", "fft", [[1.0, 2.0], [3.0]])   # ragged list
    good = svc.submit("fft", "fft", good_sig)
    svc.drain()
    with pytest.raises(RuntimeError, match="failed"):
        svc.poll(bad)
    re, _ = svc.poll(good)
    np.testing.assert_allclose(re, np.fft.fft(good_sig, axis=-1).real,
                               atol=1e-8)


def test_service_rejects_complex_fft_payload(small_world):
    """Casting complex->float64 would silently drop the imaginary plane."""
    csr, graph = small_world
    svc = KernelService(make_registry(csr, graph), n_slots=2)
    rid = svc.submit("fft", "fft",
                     RNG.standard_normal((1, 128)) * (1 + 1j))
    svc.drain()
    with pytest.raises(RuntimeError, match="complex signals"):
        svc.poll(rid)


@pytest.mark.parametrize("rows", [None, 3], ids=["1-D", "batch3"])
def test_service_fft_takes_split_planes_as_one_complex_signal(small_world,
                                                             rows):
    """A tuple (re, im) is one complex signal's planes, coalesced with a
    real signal as before."""
    csr, graph = small_world
    svc = KernelService(make_registry(csr, graph), n_slots=4)
    shape = (128,) if rows is None else (rows, 128)
    re, im = RNG.standard_normal(shape), RNG.standard_normal(shape)
    real = RNG.standard_normal((2, 128))
    split = svc.submit("fft", "fft", (re, im))
    plain = svc.submit("fft", "fft", real)
    svc.drain()
    got_re, got_im = svc.poll(split)
    want = np.atleast_2d(np.fft.fft(re + 1j * im, axis=-1))
    assert got_re.shape == want.shape
    np.testing.assert_allclose(got_re, want.real, atol=1e-8)
    np.testing.assert_allclose(got_im, want.imag, atol=1e-8)
    got_re, got_im = svc.poll(plain)
    want = np.fft.fft(real, axis=-1)
    np.testing.assert_allclose(got_re, want.real, atol=1e-8)
    np.testing.assert_allclose(got_im, want.imag, atol=1e-8)
    assert svc.stats["launches"] == 1


def test_service_refuses_mismatched_split_planes(small_world):
    csr, graph = small_world
    svc = KernelService(make_registry(csr, graph), n_slots=2)
    rid = svc.submit("fft", "fft", (RNG.standard_normal(128),
                                    RNG.standard_normal((2, 128))))
    svc.drain()
    with pytest.raises(RuntimeError, match="differ in shape"):
        svc.poll(rid)


def test_service_release_drops_delivered_results(small_world):
    csr, graph = small_world
    svc = KernelService(make_registry(csr, graph), n_slots=2)
    rid = svc.submit("spmv", "mat", RNG.standard_normal(csr.n_cols))
    with pytest.raises(ValueError, match="not finished"):
        svc.release(rid)                       # refuse: it would leak later
    svc.drain()
    assert svc.poll(rid) is not None
    svc.release(rid)
    assert not svc.completed and rid not in svc._by_rid
    with pytest.raises(KeyError):
        svc.poll(rid)
    svc.release(rid)                           # idempotent


def test_service_rejects_wrong_operand_kind(small_world):
    csr, graph = small_world
    svc = KernelService(make_registry(csr, graph), n_slots=2)
    rid = svc.submit("spmv", "graph", RNG.standard_normal(8))
    svc.drain()
    with pytest.raises(RuntimeError, match="not a matrix"):
        svc.poll(rid)


# ---------------------------------------------------------------------------
# ops.spmv repack regression: the second call must not repack
# ---------------------------------------------------------------------------


def test_spmv_second_mismatched_call_does_not_repack(monkeypatch):
    csr = F.random_csr(90, 90, 5.0, seed=2)
    ell = F.csr_to_ellpack(csr, c=16)          # packed at the "wrong" C
    x = RNG.standard_normal(90)
    cache = TuneCache()

    packs = {"n": 0}
    real = ops.csr_to_sell_slabs

    def counting(*args, **kwargs):
        packs["n"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(ops, "csr_to_sell_slabs", counting)
    y1 = np.asarray(ops.spmv(ell, x, vl=32, cache=cache))
    assert packs["n"] == 1                     # first call pays the repack
    y2 = np.asarray(ops.spmv(ell, x, vl=32, cache=cache))
    assert packs["n"] == 1                     # second call reuses it
    np.testing.assert_allclose(y1, csr.matvec(x), rtol=1e-10, atol=1e-10)
    np.testing.assert_array_equal(y1, y2)
    assert sum(cache.repacks.values()) == 1    # recorded once, not per call


def test_spmv_default_cache_memoizes_across_calls(monkeypatch):
    """Without an explicit cache the process-wide default still dedupes."""
    monkeypatch.setattr(ops, "_DEFAULT_CACHE", None)   # isolate the test
    csr = F.random_csr(70, 70, 4.0, seed=5)
    ell = F.csr_to_ellpack(csr, c=8)
    x = RNG.standard_normal(70)
    packs = {"n": 0}
    real = ops.csr_to_sell_slabs

    def counting(*args, **kwargs):
        packs["n"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(ops, "csr_to_sell_slabs", counting)
    ops.spmv(ell, x, vl=16)
    ops.spmv(ell, x, vl=16)
    assert packs["n"] == 1
    assert sum(ops.default_tune_cache().repacks.values()) == 1


# ---------------------------------------------------------------------------
# pack_tuned with a cache
# ---------------------------------------------------------------------------


def test_pack_tuned_consults_cache(small_world, count_measures):
    csr, _ = small_world
    cache = TuneCache()
    slabs1, tuned1 = ops.pack_tuned(csr, cache=cache)
    assert count_measures["n"] > 0
    count_measures["n"] = 0
    slabs2, tuned2 = ops.pack_tuned(csr, cache=cache)
    assert count_measures["n"] == 0
    assert slabs2 is slabs1                    # packed memo hit
    assert (tuned2.c, tuned2.sigma) == (tuned1.c, tuned1.sigma)
    x = RNG.standard_normal(csr.n_cols)
    np.testing.assert_allclose(
        np.asarray(ops.spmv(slabs2, x, vl=tuned2.c, w_block=tuned2.w_block)),
        csr.matvec(x), rtol=1e-10, atol=1e-10)


# ---------------------------------------------------------------------------
# Single-launch coalescing: one batched core call per (op, operand) group
# ---------------------------------------------------------------------------


def test_service_coalesces_spmv_group_into_one_spmm_launch(
        small_world, monkeypatch):
    """Five concurrent SpMV requests against one operand become ONE
    spmm_sell launch (the launch-counter hook), and every column still
    matches the host reference."""
    from repro.kernels import sell_core

    csr, graph = small_world
    reg = make_registry(csr, graph)
    svc = KernelService(reg, n_slots=8)
    calls = {"n": 0}
    real = sell_core.spmm_sell

    def counting(*args, **kwargs):
        calls["n"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(sell_core, "spmm_sell", counting)
    xs = [RNG.standard_normal(csr.n_cols) for _ in range(5)]
    rids = [svc.submit("spmv", "mat", x) for x in xs]
    svc.drain()
    assert calls["n"] == 1                     # 5 requests, one launch
    assert svc.stats["launches"] == 1
    assert reg.get("mat").launches == 1        # the per-operand hook
    assert svc.stats["coalesced"] >= 5 and svc.stats["max_group"] == 5
    for rid, x in zip(rids, xs):
        np.testing.assert_allclose(
            svc.poll(rid), csr.matvec(x), rtol=1e-10, atol=1e-10)


def test_service_coalesces_bfs_sources_into_one_batched_drive(
        small_world, monkeypatch):
    from repro.kernels import bfs as bfs_k

    csr, graph = small_world
    svc = KernelService(make_registry(csr, graph), n_slots=8)
    calls = {"n": 0}
    real = bfs_k.bfs_sell

    def counting(*args, **kwargs):
        calls["n"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(bfs_k, "bfs_sell", counting)
    sources = [0, 3, 11]
    rids = [svc.submit("bfs", "graph", source=s) for s in sources]
    svc.drain()
    assert calls["n"] == 1                     # 3 sources, one batched drive
    for rid, s in zip(rids, sources):
        np.testing.assert_array_equal(
            svc.poll(rid), G.bfs_reference(graph, s))


def test_service_coalesces_pagerank_configs_into_one_batched_drive(
        small_world, monkeypatch):
    """Requests with DIFFERENT (damping, iters) still coalesce: the configs
    become iterate columns and freeze at their own iteration budget."""
    from repro.kernels import pagerank as pr_k

    csr, graph = small_world
    svc = KernelService(make_registry(csr, graph), n_slots=8)
    calls = {"n": 0}
    real = pr_k.pagerank_sell

    def counting(*args, **kwargs):
        calls["n"] += 1
        return real(*args, **kwargs)

    monkeypatch.setattr(pr_k, "pagerank_sell", counting)
    r1 = svc.submit("pagerank", "graph", iters=4)
    r2 = svc.submit("pagerank", "graph", iters=7, damping=0.6)
    svc.drain()
    assert calls["n"] == 1
    np.testing.assert_allclose(
        svc.poll(r1), G.pagerank_reference(graph, iters=4), rtol=1e-8)
    np.testing.assert_allclose(
        svc.poll(r2), G.pagerank_reference(graph, damping=0.6, iters=7),
        rtol=1e-8)


def _hub_graph(n=200, hub=9):
    g = G.random_graph(n_nodes=n, avg_degree=5, seed=4)
    adj = np.concatenate([g.adj, np.full((n, 1), G.PAD, np.int32)], axis=1)
    adj[np.arange(n) != hub, -1] = hub
    return G.EllpackGraph(adj=adj, n_nodes=n)


def test_registry_splits_hub_rows_and_records_it():
    """The hub's in-degree (about 200) is cut to the split width: the
    operand records the width, the split nodes, the virtual rows and the
    padded slots, and serves exact answers from the split layout."""
    from repro.sparse.formats import SPLIT_WIDTH

    graph = _hub_graph()
    in_deg = G.in_degree(graph)
    reg = KernelRegistry()
    op = reg.register_graph("g", graph)
    width = op.split["split_width"]
    assert width == SPLIT_WIDTH < in_deg.max()
    assert op.split == G.split_stats(op.slabs)
    assert op.split["split_nodes"] == int((in_deg > width).sum())
    assert op.split["virtual_rows"] == int(
        np.maximum(-(-in_deg // width), 1).sum())
    assert op.split["padded_slots"] == op.slabs.padded_entries
    assert max(op.slabs.widths) <= width
    svc = KernelService(reg, n_slots=4)
    rids = [svc.submit("bfs", "g", source=s) for s in (0, 9, 17)]
    rp = svc.submit("pagerank", "g", iters=5)
    svc.drain()
    for rid, s in zip(rids, (0, 9, 17)):
        np.testing.assert_array_equal(svc.poll(rid), G.bfs_reference(graph, s))
    np.testing.assert_allclose(
        svc.poll(rp), G.pagerank_reference(graph, iters=5), rtol=1e-10)
    # three sources ride one pow2-padded 4-column drive, PageRank one column
    levels = svc.stats["graph_steps"] - 5
    assert svc.stats["graph_slots"] == op.split["padded_slots"] * (
        4 * levels + 5)


def test_graph_tune_without_split_width_is_never_served(count_measures):
    """A graph tune keyed the way unsplit layouts were keyed is never
    read: the split layout tunes under its own key, and a second
    registration hits it."""
    from repro.core.autotune import SellTuneResult

    graph = _hub_graph()
    cache = TuneCache()
    reg = KernelRegistry(cache=cache)
    old_key = cache.sell_key("graph", graph, device=reg.device,
                             machine=reg.machine)
    cache.put_sell(old_key, SellTuneResult(
        c=8, sigma=8, w_block=8, cycles=1.0, pad_factor=1.0,
        table=((8, 8, 1.0, 1.0),)))
    op = reg.register_graph("g", graph)
    assert not op.tune_was_cached and count_measures["n"] > 0
    assert cache._entries[old_key]["hits"] == 0
    (key,) = [k for k in cache._entries if k != old_key]
    assert key.startswith("graph-split|")
    count_measures["n"] = 0
    again = KernelRegistry(cache=cache).register_graph("g2", graph)
    assert again.tune_was_cached and count_measures["n"] == 0
    assert again.slabs is op.slabs             # the packed-layout memo


def test_service_bad_spmv_payload_excluded_from_batched_launch(small_world):
    """A wrong-sized x fails alone; its groupmates ride the same batched
    launch and succeed (the stacking must skip the bad column)."""
    csr, graph = small_world
    svc = KernelService(make_registry(csr, graph), n_slots=4)
    x = RNG.standard_normal(csr.n_cols)
    bad = svc.submit("spmv", "mat", RNG.standard_normal(csr.n_cols - 1))
    good = svc.submit("spmv", "mat", x)
    svc.drain()
    with pytest.raises(RuntimeError, match="must have shape"):
        svc.poll(bad)
    np.testing.assert_allclose(
        svc.poll(good), csr.matvec(x), rtol=1e-10, atol=1e-10)


# ---------------------------------------------------------------------------
# Backpressure + latency accounting
# ---------------------------------------------------------------------------


def test_service_bounded_queue_rejects_with_queue_full(small_world):
    from repro.service import QueueFull

    csr, graph = small_world
    svc = KernelService(make_registry(csr, graph), n_slots=2, max_queue=3)
    xs = [RNG.standard_normal(csr.n_cols) for _ in range(3)]
    rids = [svc.submit("spmv", "mat", x) for x in xs]
    with pytest.raises(QueueFull, match="admission queue is full"):
        svc.submit("spmv", "mat", xs[0])
    assert svc.stats["rejected"] == 1
    # stepping drains the queue and re-opens admission
    svc.step()
    rids.append(svc.submit("spmv", "mat", xs[0]))
    svc.drain()
    assert svc.stats["served"] == 4
    for rid, x in zip(rids, xs + [xs[0]]):
        np.testing.assert_allclose(
            svc.poll(rid), csr.matvec(x), rtol=1e-10, atol=1e-10)


def test_service_rejects_zero_capacity_queue(small_world):
    """max_queue=0 would make every submit raise and the documented
    reject-then-step retry spin forever — refused at construction."""
    csr, graph = small_world
    with pytest.raises(ValueError, match="max_queue must be >= 1"):
        KernelService(make_registry(csr, graph), n_slots=2, max_queue=0)


def test_service_latency_percentiles_cover_retired_requests(small_world):
    csr, graph = small_world
    svc = KernelService(make_registry(csr, graph), n_slots=4)
    assert svc.latency_percentiles() == {
        "p50_us": 0.0, "p95_us": 0.0, "p99_us": 0.0}
    for _ in range(6):
        svc.submit("spmv", "mat", RNG.standard_normal(csr.n_cols))
    svc.drain()
    pct = svc.latency_percentiles()
    assert 0 < pct["p50_us"] <= pct["p95_us"] <= pct["p99_us"]


# ---------------------------------------------------------------------------
# Cross-process TuneCache sharing (advisory file lock + merge-on-save)
# ---------------------------------------------------------------------------


_WRITER_SCRIPT = """
import sys
from repro.core.autotune import SellTuneResult
from repro.service.tunecache import TuneCache

path, worker, n_entries = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
cache = TuneCache(path)
for i in range(n_entries):
    cache.put_sell(
        f"spmv|cpu|float64|m|w{worker}e{i}",
        SellTuneResult(c=8, sigma=64, w_block=8, cycles=1.0,
                       pad_factor=1.0, table=((8, 64, 1.0, 1.0),)))
    cache.save()
"""


def test_tunecache_two_concurrent_writers_lose_nothing(tmp_path):
    """Two processes hammering save() on one cache file must union their
    entries — the advisory lock serializes the load-merge-write section.
    Fresh subprocesses (not fork: the JAX-initialized test process is
    multithreaded, and forking it risks deadlock) whose import graph never
    touches jax."""
    import os
    import subprocess
    import sys

    import repro

    # repro is a src-layout (possibly namespace) package: locate src/ from
    # its package path, not __file__ (None for namespace packages)
    src_dir = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    path = str(tmp_path / "shared.json")
    n = 12
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WRITER_SCRIPT, path, str(w), str(n)],
            env={**os.environ,
                 "PYTHONPATH": os.pathsep.join(
                     p for p in (src_dir, os.environ.get("PYTHONPATH")) if p)})
        for w in range(2)
    ]
    for p in procs:
        assert p.wait(timeout=120) == 0
    merged = TuneCache(path)
    assert len(merged) == 2 * n                # no lost writes
    for w in range(2):
        for i in range(n):
            assert merged.get_sell(f"spmv|cpu|float64|m|w{w}e{i}") is not None


def test_tunecache_interleaved_saves_merge_instead_of_clobbering(tmp_path):
    """The single-process shape of the same guarantee: two instances that
    loaded the same (empty) file and save different entries both survive."""
    from repro.core.autotune import SellTuneResult

    path = str(tmp_path / "tune.json")
    res = SellTuneResult(c=8, sigma=64, w_block=8, cycles=1.0,
                         pad_factor=1.0, table=((8, 64, 1.0, 1.0),))
    a, b = TuneCache(path), TuneCache(path)
    a.put_sell("spmv|cpu|float64|m|A", res, source="a")
    a.save()
    b.put_sell("spmv|cpu|float64|m|B", res, source="b")
    b.save()                                   # must fold A's entry in
    merged = TuneCache(path)
    assert len(merged) == 2
    # hints merge too; repack counts are event tallies, so two workers
    # each observing one event total two
    a.set_hint("spmv", "m1", 64)
    a.note_repack("r")
    a.save()
    b.note_repack("r")
    b.save()
    merged = TuneCache(path)
    assert merged.hint_vl("spmv", "m1") == 64
    assert merged.repacks["r"] == 2


def test_tunecache_save_does_not_revert_keys_it_only_loaded(tmp_path):
    """Merge-on-save overlays only keys THIS instance wrote: a worker that
    loaded a key and then saves unrelated work must not roll back another
    worker's newer value for it."""
    from repro.core.autotune import SellTuneResult

    path = str(tmp_path / "tune.json")
    res = SellTuneResult(c=8, sigma=64, w_block=8, cycles=1.0,
                         pad_factor=1.0, table=((8, 64, 1.0, 1.0),))
    seed = TuneCache(path)
    seed.set_hint("spmv", "m1", 64)
    seed.save()
    stale = TuneCache(path)                    # loads h=64, never writes it
    fresh = TuneCache(path)
    fresh.set_hint("spmv", "m1", 128)          # another worker updates it
    fresh.save()
    stale.put_sell("spmv|cpu|float64|m|X", res)
    stale.save()                               # unrelated write
    merged = TuneCache(path)
    assert merged.hint_vl("spmv", "m1") == 128  # newer value survived
    assert merged.get_sell("spmv|cpu|float64|m|X") is not None


def test_tunecache_hit_counters_accumulate_across_workers(tmp_path):
    """The persisted per-entry 'hits' tally sums concurrent workers'
    increments instead of one worker's save reverting the other's."""
    from repro.core.autotune import SellTuneResult

    path = str(tmp_path / "tune.json")
    key = "spmv|cpu|float64|m|K"
    res = SellTuneResult(c=8, sigma=64, w_block=8, cycles=1.0,
                         pad_factor=1.0, table=((8, 64, 1.0, 1.0),))
    seed = TuneCache(path)
    seed.put_sell(key, res)
    seed.save()
    a, b = TuneCache(path), TuneCache(path)
    for _ in range(2):
        a.get_sell(key)
    for _ in range(3):
        b.get_sell(key)
    a.save()
    b.save()
    merged = TuneCache(path)
    assert merged._entries[key]["hits"] == 5


# ---------------------------------------------------------------------------
# bench_service smoke (tiny): the CI artifact shape
# ---------------------------------------------------------------------------


def test_bench_service_emits_load_levels_and_tune_rows():
    bench_service = pytest.importorskip(
        "benchmarks.bench_service",
        reason="benchmarks namespace package needs the repo root on sys.path")
    table = bench_service.bench_load(loads=(2, 4, 6), n_slots=4,
                                     with_bfs=False)
    assert sorted(table) == [
        "service_load_2", "service_load_4", "service_load_6",
        "service_load_6_uncoalesced"]
    for entry in table.values():
        assert entry["served"] == entry["offered"]
        assert entry["us_per_call"] > 0 and entry["throughput_rps"] > 0
        # the latency/backpressure/launch fields the CI gate tracks
        assert 0 < entry["p50_us"] <= entry["p95_us"] <= entry["p99_us"]
        assert entry["rejected"] == 0          # tiny loads never backpressure
        assert 0 < entry["launches"] <= entry["groups"]
    # the headline is self-contained: the top level records its speedup
    # over the 1-wide uncoalesced counterfactual measured in the same run
    assert table["service_load_6"]["coalescing_speedup"] > 0
    assert table["service_load_6_uncoalesced"]["launches"] == 6
