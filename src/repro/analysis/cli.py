"""``python -m repro.analysis`` — the repo's static-analysis gate.

Default run lints the given paths (default: ``src``) with every shipped
rule and exits non-zero on any finding; this is the CI merge gate.  The
lint path is stdlib + numpy only — no JAX import — so the gate is cheap
and cannot be wedged by the code it checks.

``--plans`` additionally runs the launch-plan preflight self-check: builds
representative operands (a random CSR matrix, a random graph, an FFT
config) with the repo's own generators, derives the static
:class:`~repro.analysis.launchplan.LaunchPlan` for every Pallas entry
point, prints each plan table, and fails if any contract is violated —
i.e. it proves the shipped tuning heuristics still land inside the
modeled VMEM envelope without compiling or executing a single kernel.
"""
from __future__ import annotations

import argparse
import sys

from repro.analysis.lint import DEFAULT_EXCLUDE, lint_paths

__all__ = ["main"]


def _self_check_plans(out=sys.stdout) -> int:
    """Derive plans for representative operands of every entry point."""
    from repro.analysis.preflight import (
        SlabMeta,
        plan_bfs_sell,
        plan_fft_stockham,
        plan_ft,
        plan_moe_dispatch,
        plan_pagerank_sell,
        plan_spmm_sell,
        plan_spmm_sell_sharded,
        plan_spmm_sell_stream,
    )
    from repro.graphs.gen import graph_to_sell_slabs, random_graph
    from repro.sparse.formats import csr_to_sell_slabs, random_csr

    csr = random_csr(2048, 2048, avg_nnz_row=16, seed=0)
    mat = SlabMeta.from_slabs(csr_to_sell_slabs(csr, c=8), check_bounds=True)
    graph = random_graph(2048, avg_degree=8, seed=0)
    gm = SlabMeta.from_slabs(graph_to_sell_slabs(graph, c=8),
                             check_bounds=True)
    # a routing-shaped operand for the MoE dispatch entry point: exactly
    # top_k=2 stored entries per token row (the router weights), the shape
    # the LM serving path packs every step
    import numpy as np

    from repro.sparse.formats import CSRMatrix

    rng = np.random.default_rng(1)
    n_tok, n_slots, top_k = 256, 512, 2
    routing = CSRMatrix(
        indptr=np.arange(n_tok + 1, dtype=np.int64) * top_k,
        indices=np.concatenate([
            rng.choice(n_slots, top_k, replace=False)
            for _ in range(n_tok)]).astype(np.int32),
        data=rng.random(n_tok * top_k),
        n_cols=n_slots)
    rm = SlabMeta.from_slabs(csr_to_sell_slabs(routing, c=8),
                             check_bounds=True)
    plans = [
        plan_spmm_sell(mat, k=1, x_dtype="float64"),
        plan_spmm_sell(mat, k=8, x_dtype="float64"),
        plan_spmm_sell_stream(mat, k=8, x_dtype="float64"),
        plan_spmm_sell_sharded(mat, k=8, x_dtype="float64", n_devices=4,
                               window_cols=1024),
        plan_bfs_sell(gm, k=8),
        plan_pagerank_sell(gm, k=8),
        plan_fft_stockham(n=1024, batch=32),
        plan_ft((512, 512, 512), dtype="float32"),
        plan_moe_dispatch(rm, k=64, x_dtype="float64", top_k=2),
    ]
    bad = 0
    for plan in plans:
        print(plan.table(), file=out)
        bad += 0 if plan.ok else 1
    # The streaming path exists for operands the resident plan honestly
    # rejects: prove the rejection -> acceptance pair on a synthetic
    # million-row operand (metadata only — nothing is packed or launched).
    giant = SlabMeta(
        kind="matrix", c=512, widths=(8,), n_slices=(1 << 11,),
        n_rows=1 << 20, n_cols=1 << 20, val_dtype="float64",
        idx_dtype="int32")
    reject = plan_spmm_sell(giant, k=8, x_dtype="float64")
    accept = plan_spmm_sell_stream(giant, k=8, x_dtype="float64")
    print(accept.table(), file=out)
    if reject.ok:
        print("EXPECTED-REJECT FAILED: resident plan accepted the "
              f"giant operand {giant.describe()}", file=out)
        bad += 1
    # the routing contract: a general matrix (rows wider than top_k) must
    # be refused by the MoE dispatch plan — those weights are not a
    # token->slot routing and the combine would be silently wrong
    not_routing = plan_moe_dispatch(mat, k=64, x_dtype="float64", top_k=2)
    if not_routing.ok:
        print("EXPECTED-REJECT FAILED: moe_dispatch plan accepted a "
              f"non-routing operand {mat.describe()}", file=out)
        bad += 1
    # NPB FT class D (2048 x 1024 x 1024) holds 17 GB of spectrum alone:
    # the plan must refuse it on one chip's HBM
    class_d = plan_ft((2048, 1024, 1024), dtype="float32")
    if class_d.ok:
        print("EXPECTED-REJECT FAILED: ft plan accepted NPB FT class D "
              "on one chip", file=out)
        bad += 1
    if not accept.ok:
        bad += 1
    else:
        plans.append(accept)
    print(f"launch-plan self-check: {len(plans) - bad}/{len(plans)} ok "
          "(+ giant-operand resident rejection, non-routing "
          "moe_dispatch rejection and NPB FT class D HBM rejection "
          "proved)",
          file=out)
    return bad


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="static launch-contract checker and repo lint engine",
    )
    parser.add_argument(
        "paths", nargs="*", default=["src"],
        help="files or directories to lint (default: src)")
    parser.add_argument(
        "--strict", action="store_true",
        help="also fail on warnings and on suppressions that suppress "
             "nothing (the nightly gate)")
    parser.add_argument(
        "--rules", default=None,
        help="comma-separated rule subset (default: all shipped rules)")
    parser.add_argument(
        "--list-rules", action="store_true",
        help="print the shipped rule table and exit")
    parser.add_argument(
        "--plans", action="store_true",
        help="also run the launch-plan preflight self-check on "
             "representative operands")
    parser.add_argument(
        "--exclude", default=",".join(DEFAULT_EXCLUDE),
        help="comma-separated directory basenames to skip "
             f"(default: {','.join(DEFAULT_EXCLUDE)})")
    args = parser.parse_args(argv)

    if args.list_rules:
        from repro.analysis.rules import ALL_RULES
        for rule in ALL_RULES:
            print(f"{rule.name:28s} {rule.description}")
        return 0

    rules = None
    if args.rules:
        rules = [r.strip() for r in args.rules.split(",") if r.strip()]
    exclude = tuple(e.strip() for e in args.exclude.split(",") if e.strip())

    findings = lint_paths(args.paths, rules=rules, strict=args.strict,
                          exclude=exclude)
    for f in findings:
        print(f)
    bad_plans = _self_check_plans() if args.plans else 0
    n = len(findings)
    if n or bad_plans:
        print(f"repro.analysis: {n} finding(s)"
              + (f", {bad_plans} bad plan(s)" if args.plans else ""))
        return 1
    print("repro.analysis: clean"
          + (", all plans ok" if args.plans else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
