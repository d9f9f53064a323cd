"""Tests for the static-analysis subsystem (repro.analysis).

Covers both engines and their enforcement points:

* every shipped lint rule fires exactly once on its known-bad fixture (and
  never on another fixture), suppressions work, the live tree is clean;
* the launch-plan preflight accepts in-envelope operands and rejects
  over-VMEM / dtype-mismatch / OOB-index / non-pow2 plans with structured
  violations;
* `KernelService` rejects an infeasible operand at admission with
  `LaunchPlanError` (no kernel launch, counter incremented), and the
  registry rejects a poisoned cached tune at registration;
* the CLI exits 0 on clean input and non-zero on each fixture.
"""
import dataclasses
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import repro
from repro.analysis import (
    LaunchPlanError,
    SlabMeta,
    lint_paths,
    plan_bfs_sell,
    plan_fft_stockham,
    plan_pagerank_sell,
    plan_spmm_sell,
)
from repro.analysis.lint import lint_file
from repro.analysis.rules import ALL_RULES, resolve_rules
from repro.sparse import formats as F

TESTS_DIR = os.path.dirname(os.path.abspath(__file__))
SRC_ROOT = list(repro.__path__)[0]
BADCODE = os.path.join(TESTS_DIR, "fixtures", "badcode")

#: fixture file -> the ONE rule it must fire, exactly once
EXPECTED = {
    "bad_compat.py": "compat-discipline",
    "bad_lock.py": "tunecache-lock-discipline",
    "bad_async.py": "async-hygiene",
    "bad_kernel.py": "kernel-purity",
    "bad_vmem.py": "vmem-budget-literal",
    "bad_timer.py": "timer-discipline",
}


def _meta(**over):
    """A small, healthy matrix SlabMeta; override fields to break it."""
    base = dict(
        kind="matrix", c=8, widths=(8, 16), n_slices=(4, 2),
        n_rows=48, n_cols=48, val_dtype="float64", idx_dtype="int32",
        idx_min=-1, idx_max=47,
    )
    base.update(over)
    return SlabMeta(**base)


# ---------------------------------------------------------------------------
# Lint engine: fixtures, suppressions, live tree
# ---------------------------------------------------------------------------


def test_every_shipped_rule_has_a_fixture():
    assert set(EXPECTED.values()) == {r.name for r in ALL_RULES}


@pytest.mark.parametrize("fname,rule", sorted(EXPECTED.items()))
def test_fixture_fires_its_rule_exactly_once(fname, rule):
    findings = lint_paths([os.path.join(BADCODE, fname)])
    assert [f.rule for f in findings] == [rule], \
        f"{fname}: expected exactly one {rule} finding, got {findings}"


def test_fixtures_do_not_cross_fire():
    """No fixture triggers a rule other than its own (rules are precise)."""
    for fname, rule in EXPECTED.items():
        findings = lint_paths([os.path.join(BADCODE, fname)])
        assert {f.rule for f in findings} <= {rule}, (fname, findings)


def test_badcode_dir_excluded_from_directory_walk():
    """The default walk refuses to enter the known-bad corpus, so linting
    the tests tree stays clean even though every fixture is broken."""
    findings = lint_paths([os.path.join(TESTS_DIR, "fixtures")])
    assert findings == []


def test_live_tree_is_clean():
    """The merged src + tests tree passes every shipped rule — the CI
    merge-gate invariant, asserted in-process."""
    findings = lint_paths([SRC_ROOT, TESTS_DIR])
    assert findings == [], "\n".join(str(f) for f in findings)


def test_line_suppression(tmp_path):
    bad = tmp_path / "sup.py"
    bad.write_text(
        "import time\n"
        "async def f():\n"
        "    time.sleep(1)  # lint-ok: async-hygiene\n")
    assert lint_paths([str(bad)]) == []


def test_file_suppression(tmp_path):
    bad = tmp_path / "supf.py"
    bad.write_text(
        "# lint-ok-file: async-hygiene\n"
        "import time\n"
        "async def f():\n"
        "    time.sleep(1)\n"
        "async def g():\n"
        "    time.sleep(2)\n")
    assert lint_paths([str(bad)]) == []


def test_strict_flags_unused_suppression(tmp_path):
    clean = tmp_path / "unused.py"
    clean.write_text(
        "# lint-ok-file: kernel-purity\n"
        "x = 1  # lint-ok: async-hygiene\n")
    assert lint_paths([str(clean)]) == []          # default: silent
    strict = lint_paths([str(clean)], strict=True)
    assert sorted(f.rule for f in strict) == ["unused-suppression"] * 2


def test_timer_rule_inactive_without_participation(tmp_path):
    """A module that neither lives under service/serve nor imports the
    serving layer at top level may read the raw clock freely."""
    f = tmp_path / "standalone.py"
    f.write_text("import time\nT0 = time.perf_counter()\n")
    assert lint_paths([str(f)]) == []


def test_timer_rule_nested_import_does_not_participate(tmp_path):
    """A lazy (function-local) import of the serving layer — the ops/bench
    layering idiom — must not opt the whole module into the timer rule."""
    f = tmp_path / "lazy.py"
    f.write_text(
        "import time\n"
        "def helper():\n"
        "    from repro.service.tunecache import TuneCache\n"
        "    return TuneCache, time.perf_counter()\n")
    assert lint_paths([str(f)]) == []


def test_timer_rule_top_level_import_participates(tmp_path):
    f = tmp_path / "servingish.py"
    f.write_text(
        "import time\n"
        "from repro.serve.slots import SlotLoop\n"
        "T0 = time.perf_counter()\n"
        "T1 = time.time()\n")
    findings = lint_paths([str(f)])
    assert [x.rule for x in findings] == ["timer-discipline"] * 2


def test_timer_rule_lint_ok_escape(tmp_path):
    f = tmp_path / "escaped.py"
    f.write_text(
        "import time\n"
        "from repro.serve.slots import SlotLoop\n"
        "T0 = time.perf_counter()  # lint-ok: timer-discipline\n")
    assert lint_paths([str(f)]) == []


def test_syntax_error_is_a_finding(tmp_path):
    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n")
    findings = lint_paths([str(bad)])
    assert [f.rule for f in findings] == ["syntax-error"]


def test_unknown_rule_name_rejected():
    with pytest.raises(KeyError, match="no-such-rule"):
        resolve_rules(["no-such-rule"])


def test_rule_subset_runs_only_requested(tmp_path):
    findings = lint_file(os.path.join(BADCODE, "bad_async.py"),
                         resolve_rules(["kernel-purity"]))
    assert findings == []


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _run_cli(*args):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(TESTS_DIR), "src")
    return subprocess.run(
        [sys.executable, "-m", "repro.analysis", *args],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(TESTS_DIR))


def test_cli_clean_on_live_tree():
    proc = _run_cli("src")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "clean" in proc.stdout


@pytest.mark.parametrize("fname,rule", sorted(EXPECTED.items()))
def test_cli_nonzero_on_each_fixture(fname, rule):
    proc = _run_cli(os.path.join("tests", "fixtures", "badcode", fname))
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert rule in proc.stdout


def test_cli_list_rules():
    proc = _run_cli("--list-rules")
    assert proc.returncode == 0
    for rule in ALL_RULES:
        assert rule.name in proc.stdout


# ---------------------------------------------------------------------------
# Launch-plan preflight: contracts
# ---------------------------------------------------------------------------


def test_plan_ok_on_healthy_meta():
    plan = plan_spmm_sell(_meta(), k=4, x_dtype="float64")
    assert plan.ok
    assert plan.n_launches == 2
    assert plan.grid_cells > 0
    assert 0 < plan.peak_vmem_bytes < plan.vmem_budget
    assert plan.raise_if_invalid() is plan
    summary = plan.summary()
    assert summary["ok"] and summary["violations"] == []
    assert "spmm_sell" in plan.table()


def test_plan_over_vmem_rejected():
    meta = _meta(n_cols=1 << 24, idx_max=(1 << 24) - 1)
    plan = plan_spmm_sell(meta, k=8, x_dtype="float64")
    assert not plan.ok
    assert any("VMEM budget" in v for v in plan.violations)
    with pytest.raises(LaunchPlanError) as exc:
        plan.raise_if_invalid()
    assert exc.value.kernel == "spmm_sell"
    assert exc.value.plan is plan


def test_plan_dtype_mismatch_rejected():
    plan = plan_spmm_sell(_meta(), k=2, x_dtype="float32")
    assert any("float32" in v and "float64" in v for v in plan.violations)
    plan = plan_spmm_sell(_meta(), k=2, x_dtype="int32")
    assert any("not floating" in v for v in plan.violations)


def test_plan_oob_index_rejected():
    plan = plan_spmm_sell(_meta(idx_max=48), k=1, x_dtype="float64")
    assert any("out of bounds" in v for v in plan.violations)
    plan = plan_spmm_sell(_meta(idx_min=-2), k=1, x_dtype="float64")
    assert any("PAD sentinel" in v for v in plan.violations)


def test_plan_pow2_invariants_rejected():
    plan = plan_spmm_sell(_meta(widths=(8, 12)), k=1)
    assert any("not a power of two" in v for v in plan.violations)
    plan = plan_spmm_sell(_meta(), k=1, w_block=6)
    assert any("w_block 6" in v for v in plan.violations)
    plan = plan_spmm_sell(_meta(), k=1, k_block=3)
    assert any("k_block 3" in v for v in plan.violations)


def test_plan_bad_index_dtype_rejected():
    plan = plan_spmm_sell(_meta(idx_dtype="int64"), k=1)
    assert any("int32" in v for v in plan.violations)


def test_graph_plans():
    gmeta = _meta(kind="graph", val_dtype=None)
    assert plan_bfs_sell(gmeta, k=8).ok
    assert plan_pagerank_sell(gmeta, k=8).ok
    big = _meta(kind="graph", val_dtype=None, n_rows=1 << 24,
                n_cols=1 << 24, idx_max=(1 << 24) - 1)
    assert not plan_pagerank_sell(big, k=64).ok


def test_fft_plans():
    assert plan_fft_stockham(1024, batch=16).ok
    bad = plan_fft_stockham(1000, batch=16)
    assert any("power of two" in v for v in bad.violations)
    huge = plan_fft_stockham(1 << 22, batch=8)
    assert any("VMEM budget" in v for v in huge.violations)


def test_slab_meta_from_real_slabs():
    csr = F.random_csr(100, 90, 5.0, seed=3)
    slabs = F.csr_to_sell_slabs(csr, c=16)
    meta = SlabMeta.from_slabs(slabs, check_bounds=True)
    assert meta.kind == "matrix"
    assert meta.c == 16
    assert meta.n_rows == 100 and meta.n_cols == 90
    assert all(w >= 1 and (w & (w - 1)) == 0 for w in meta.widths)
    assert meta.idx_max is not None and meta.idx_max < 90
    assert meta.idx_min >= -1
    assert plan_spmm_sell(meta, k=4, x_dtype=meta.val_dtype).ok


def test_slab_meta_from_graph_slabs():
    from repro.graphs.gen import graph_to_sell_slabs, random_graph

    g = random_graph(200, avg_degree=4, seed=1)
    meta = SlabMeta.from_slabs(graph_to_sell_slabs(g, c=8),
                               check_bounds=True)
    assert meta.kind == "graph"
    assert meta.val_dtype is None
    assert plan_bfs_sell(meta, k=4).ok


@pytest.mark.parametrize("graph", ["hub-free", "rmat", "hub"])
def test_graph_layout_meta_prices_the_split_slabs(graph):
    """The preflight's metadata, from the in-degrees alone, is the
    metadata of the slabs the packer builds, split rows included."""
    from repro.graphs.gen import (
        PAD,
        EllpackGraph,
        graph_to_sell_slabs,
        in_degree,
        random_graph,
        rmat_graph,
        split_stats,
    )
    from repro.service.registry import _graph_layout_meta

    if graph == "hub-free":
        g = random_graph(512, avg_degree=6, seed=3)
    else:
        g = rmat_graph(512, avg_degree=8, seed=3)
    if graph == "hub":                         # node 7: in-degree >= 511
        adj = np.concatenate([g.adj, np.full((512, 1), PAD, np.int32)], 1)
        adj[np.arange(512) != 7, -1] = 7
        g = EllpackGraph(adj=adj, n_nodes=512)
    slabs = graph_to_sell_slabs(g, c=32, sigma=128, reverse=True)
    assert (split_stats(slabs)["split_nodes"] > 0) == (graph != "hub-free")
    assert _graph_layout_meta(in_degree(g), 32, 128) == \
        SlabMeta.from_slabs(slabs)


def _graph500_in_degree(scale: int) -> np.ndarray:
    """In-degrees of the benchmark's Graph500 graph (Kronecker A, B, C =
    0.57, 0.19, 0.19, edge factor 16, undirected, self-loops out), from
    its edge list: the degree-padded input would take gigabytes."""
    import importlib.util

    path = os.path.join(os.path.dirname(__file__), "..", "bench",
                        "configs", "graph500-s14.py")
    spec = importlib.util.spec_from_file_location("graph500_config", path)
    config = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(config)
    n = 1 << scale
    _, dst = config.undirected(
        config.kronecker_edges(scale, 16, 0.57, 0.19, 0.19, 0), n)
    return np.bincount(dst, minlength=n)


def test_graph500_scale15_fits_once_hub_rows_split():
    """Unsplit, the scale-15 hub slice is refused (its tile exceeds
    VMEM); with the hub rows split, as the registry tunes and packs
    them, every bucket fits at the chip's float32."""
    from repro.graphs.gen import split_rows
    from repro.kernels.ops import cached_tune_sell
    from repro.service.registry import KernelRegistry, _graph_layout_meta
    from repro.sparse.formats import (
        SPLIT_WIDTH,
        next_pow2,
        sigma_sort_order,
        slice_widths,
    )

    in_deg = _graph500_in_degree(15)
    assert in_deg.max() > SPLIT_WIDTH
    reg = KernelRegistry(device="tpu")
    tuned = cached_tune_sell(split_rows(in_deg)[1], n_cols=len(in_deg),
                             machine=reg.machine)
    meta = _graph_layout_meta(in_deg, tuned.c, tuned.sigma)
    assert plan_bfs_sell(meta).ok
    assert plan_pagerank_sell(meta, dtype="float32").ok
    assert max(meta.widths) <= SPLIT_WIDTH
    # the same (C, sigma) with one row per node, as before the split
    widths = next_pow2(slice_widths(
        in_deg, sigma_sort_order(in_deg, tuned.sigma), tuned.c))
    uniq, counts = np.unique(widths, return_counts=True)
    whole = SlabMeta(
        kind="graph", c=tuned.c, widths=tuple(int(w) for w in uniq),
        n_slices=tuple(int(s) for s in counts), n_rows=len(in_deg),
        n_cols=len(in_deg), val_dtype=None, idx_dtype="int32")
    assert not plan_pagerank_sell(whole, dtype="float32").ok


def test_slab_meta_rejects_unknown_container():
    with pytest.raises(TypeError, match="SellSlabs"):
        SlabMeta.from_slabs(object())


# ---------------------------------------------------------------------------
# Enforcement: kernels/ops entry points
# ---------------------------------------------------------------------------


def test_ops_spmm_rejects_non_pow2_w_block():
    from repro.kernels import ops

    csr = F.random_csr(60, 60, 4.0, seed=2)
    x = np.ones((60, 2))
    with pytest.raises(LaunchPlanError, match="w_block 6"):
        ops.spmm(csr, x, vl=8, w_block=6)


def test_ops_spmm_rejects_dtype_mismatch():
    from repro.kernels import ops

    csr = F.random_csr(60, 60, 4.0, seed=2)   # float64 values
    x = np.ones((60, 2), np.float32)
    with pytest.raises(LaunchPlanError, match="float32"):
        ops.spmm(csr, x, vl=8)


# ---------------------------------------------------------------------------
# Enforcement: service admission + registry
# ---------------------------------------------------------------------------


@pytest.fixture
def matrix_service():
    from repro.service.registry import KernelRegistry
    from repro.service.service import KernelService

    reg = KernelRegistry()
    reg.register_matrix("m", F.random_csr(64, 64, 4.0, seed=5))
    return KernelService(reg, n_slots=4, interpret=True)


def test_service_rejects_infeasible_operand_at_admission(matrix_service):
    svc = matrix_service
    record = svc.registry.get("m")
    good_tuned = record.tuned
    # drift the tuned tiles out of the modeled envelope AFTER registration
    # (a poisoned cache entry or a bad hand-edit would look the same):
    # k_block stays pow2 so the ONLY violated contract is the VMEM budget
    record.tuned = dataclasses.replace(good_tuned, k_block=1 << 24)
    with pytest.raises(LaunchPlanError, match="VMEM budget"):
        svc.submit("spmv", "m", np.ones(64))
    assert svc.stats["preflight_rejected"] == 1
    assert svc.stats["launches"] == 0          # no kernel launch happened
    assert svc.stats["submitted"] == 0         # rejected AT admission
    # restore: the same operand is admitted and served normally
    record.tuned = good_tuned
    rid = svc.submit("spmv", "m", np.ones(64))
    svc.drain()
    y = svc.poll(rid)
    assert y is not None and y.shape == (64,)
    assert svc.stats["launches"] == 1
    assert svc.stats["preflight_rejected"] == 1


def test_service_plans_observability(matrix_service):
    svc = matrix_service
    plans = svc.plans()
    assert set(plans) == {"m"}
    spmv = plans["m"]["spmv"]
    assert spmv["ok"] is True
    assert spmv["kernel"] == "spmm_sell"
    assert 0 < spmv["peak_vmem_bytes"] <= spmv["vmem_budget"]


def test_registry_stores_plans_and_meta(matrix_service):
    record = matrix_service.registry.get("m")
    assert record.slab_meta is not None
    assert record.slab_meta.idx_max is not None      # bounds were scanned
    assert record.plans["spmv"].ok


def test_registry_rejects_poisoned_cached_tune():
    from repro.core.autotune import SellTuneResult
    from repro.service.registry import KernelRegistry
    from repro.service.tunecache import operand_signature

    reg = KernelRegistry()
    csr = F.random_csr(64, 64, 4.0, seed=6)
    key = reg.cache.sell_key(
        "spmv", operand_signature(csr), device=reg.device,
        dtype=str(csr.data.dtype), machine=reg.machine)
    # a cached tune whose k_block drifted out of the VMEM envelope: the
    # cache answers without measuring, and registration must refuse it
    reg.cache.put_sell(key, SellTuneResult(
        c=8, sigma=64, w_block=8, cycles=1.0, pad_factor=1.0,
        table=((8, 64, 1.0, 1.0),), k_block=1 << 24))
    with pytest.raises(LaunchPlanError, match="VMEM budget"):
        reg.register_matrix("poisoned", csr)
    assert "poisoned" not in reg


def test_graph_and_fft_registration_records_plans():
    from repro.graphs.gen import random_graph
    from repro.service.registry import KernelRegistry

    reg = KernelRegistry()
    g = reg.register_graph("g", random_graph(128, avg_degree=4, seed=7))
    assert g.plans["bfs"].ok and g.plans["pagerank"].ok
    f = reg.register_fft("f", 256)
    assert f.plans["fft"].ok


# ---------------------------------------------------------------------------
# TuneCache lock degrade surfacing
# ---------------------------------------------------------------------------


def test_tunecache_lock_degrade_counted_and_warned_once(tmp_path, monkeypatch):
    from repro.service import tunecache as tc

    path = str(tmp_path / "tunes.json")
    monkeypatch.setattr(tc, "fcntl", None)           # non-POSIX platform
    monkeypatch.setattr(tc, "_DEGRADE_WARNED", False)
    cache = tc.TuneCache(path=path)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        cache.save()
        cache.save()
    degrade = [w for w in caught if "degraded" in str(w.message)]
    assert len(degrade) == 1                         # warned exactly once
    assert cache.lock_degraded == 2                  # ...but every section counted
    assert cache.stats["lock_degraded"] == 2


def test_tunecache_lock_not_degraded_with_fcntl(tmp_path):
    from repro.service.tunecache import TuneCache

    cache = TuneCache(path=str(tmp_path / "tunes.json"))
    cache.save()
    assert cache.lock_degraded == 0
    assert cache.stats["lock_degraded"] == 0


def test_tunecache_memory_only_never_degrades(monkeypatch):
    from repro.service import tunecache as tc

    monkeypatch.setattr(tc, "fcntl", None)
    cache = tc.TuneCache()                           # path=None: in-memory
    with cache._locked():
        pass
    assert cache.lock_degraded == 0                  # nothing to protect
