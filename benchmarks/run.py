"""Benchmark entry point: one table per paper figure + the roofline table.

``PYTHONPATH=src python -m benchmarks.run`` prints CSV:
  name,us_per_call,derived   (kernel microbenches)
plus the fig3/fig4/fig5 sweep tables and, when dry-run artifacts exist under
results/dryrun/, the roofline summary.  The kernel microbench table is also
written machine-readable to ``BENCH_kernels.json`` (name -> us_per_call,
pad_factor, ...) for CI artifact upload and trend tracking.

Sweep evaluation goes through the campaign engine: each requested grid is one
vectorized cube (``repro.core.campaign``), persisted to the schema-versioned
``BENCH_sweeps.json`` store, and the figure tables are projections of the
stored cube — nothing re-loops over per-point model runs.

``--kernels-only`` runs just the microbench table + JSON emission (the CI
bench smoke step).  ``--campaign NAME`` (repeatable; see
``repro.core.campaign.campaign_names``) runs named campaigns only and emits
their tables from the store.  ``--check-claims`` additionally validates the
paper's two claims on the fig3/fig5 cubes and exits nonzero on violations —
the CI ``paper-claims`` merge gate.  ``--measure`` attaches Pallas
interpret-mode timings to each campaign record set.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax

jax.config.update("jax_enable_x64", True)


def _emit_kernels(json_path: str) -> dict:
    from benchmarks import bench_kernels

    table = bench_kernels.collect()
    print("# table: kernel microbenchmarks (name,us_per_call,derived)")
    bench_kernels.main(precomputed=table)
    with open(json_path, "w") as f:
        json.dump(table, f, indent=2, sort_keys=True)
    print(f"# wrote {json_path}")
    return table


def _print_crosscheck(name: str, result) -> None:
    from repro.core.campaign import crosscheck_measured

    rows = crosscheck_measured(result)
    if not rows:
        return
    print(f"\n# table: campaign {name} model-vs-measured "
          "(kernel,vl,problem,modeled_cycles,measured_us,cycles_per_us)")
    for row in rows:
        print(f"{row['kernel']},{row['vl']},{row['problem']},"
              f"{row['modeled_cycles']:.0f},"
              f"{row['measured_us']:.1f},{row['cycles_per_us']:.1f}")


def _emit_campaign_table(name: str, result) -> None:
    """Print the figure table a campaign corresponds to, from its cube."""
    from benchmarks import bench_bandwidth, bench_latency, bench_slowdown
    from repro.core.sweep import sweep_result_from_campaign

    if name == "paper-fig3":
        print("\n# table: paper Fig 3 (kernel,series,extra_latency,cycles,us)")
        bench_latency.main(precomputed=sweep_result_from_campaign(result))
    elif name == "paper-fig4":
        print("\n# table: paper Fig 4 "
              "(kernel,series,extra_latency,slowdown[,paper,rel_err])")
        bench_slowdown.main(precomputed=sweep_result_from_campaign(result))
    elif name == "paper-fig5":
        print("\n# table: paper Fig 5 (kernel,series,bw_limit,normalized_time)")
        bench_bandwidth.main(precomputed=sweep_result_from_campaign(result))
    else:
        print(f"\n# table: campaign {name} "
              "(machine,kernel,vl,extra_latency,bw_limit,cycles,source)")
        for r in result.records():
            print(f"{r['machine']},{r['kernel']},{r['vl']},{r['extra_latency']},"
                  f"{r['bw_limit']},{r.get('cycles', '')},{r['source']}")


def _check_claims(store) -> list[str]:
    """The paper's two claims, evaluated from the persisted cubes."""
    from repro.core.sweep import (
        check_bandwidth_claim,
        check_latency_claim,
        slowdown_tables,
        sweep_result_from_campaign,
    )

    fig3 = sweep_result_from_campaign(store.get("paper-fig3"))
    fig5 = sweep_result_from_campaign(store.get("paper-fig5"))
    return (check_latency_claim(slowdown_tables(fig3))
            + check_bandwidth_claim(fig5))


def run_campaigns(names, sweeps_json: str, measure: bool = False,
                  check_claims: bool = False) -> int:
    """Run named campaigns -> store -> tables (and optionally the claim gate).

    Returns a process exit code (0 ok, 1 claim violations)."""
    from repro.core.campaign import SweepStore, run_campaign

    if check_claims:
        # the claim gate needs both knob cubes
        names = list(dict.fromkeys(list(names) + ["paper-fig3", "paper-fig5"]))
    store = SweepStore(sweeps_json)
    for name in names:
        result = run_campaign(name, measure=measure)
        store.put(result)
        print(f"# campaign {name}: {result.spec.n_points} modeled points "
              f"({'x'.join(map(str, result.spec.shape))} cube)")
        _emit_campaign_table(name, result)
        if measure and result.measured:
            _print_crosscheck(name, result)
    store.save()
    print(f"# wrote {store.path} ({', '.join(store.names())})")
    if check_claims:
        violations = _check_claims(store)
        if violations:
            print("# PAPER CLAIM VIOLATIONS:")
            for v in violations:
                print(f"#   {v}")
            return 1
        print("# paper claims: latency-tolerance HOLDS, "
              "bandwidth-exploitation HOLDS")
    return 0


def main(argv=None) -> None:
    from repro.core.campaign import campaign_names

    ap = argparse.ArgumentParser()
    ap.add_argument("--kernels-only", action="store_true",
                    help="only the kernel microbench table + JSON")
    ap.add_argument("--json", default="BENCH_kernels.json",
                    help="machine-readable kernel table output path")
    ap.add_argument("--campaign", action="append", default=None,
                    metavar="NAME", choices=campaign_names(),
                    help="run a named sweep campaign (repeatable); "
                         f"one of {campaign_names()}")
    ap.add_argument("--sweeps-json", default="BENCH_sweeps.json",
                    help="schema-versioned campaign results store")
    ap.add_argument("--check-claims", action="store_true",
                    help="validate the paper's two claims on the fig3/fig5 "
                         "cubes; exit 1 on violations (CI merge gate)")
    ap.add_argument("--measure", action="store_true",
                    help="attach Pallas interpret-mode timings to each "
                         "campaign (slow)")
    args = ap.parse_args(argv)
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    if args.campaign or args.check_claims:
        sys.exit(run_campaigns(args.campaign or [], args.sweeps_json,
                               measure=args.measure,
                               check_claims=args.check_claims))

    kernel_table = _emit_kernels(args.json)
    if args.kernels_only:
        return

    # Full run: evaluate the paper grid as campaigns (fig4 shares the fig3
    # cube), persist the store, and print every figure table from it.  The
    # microbench wall times just collected ride along as measured records in
    # the same store schema; --measure adds the dedicated interpret-mode
    # timing pass on top.
    from benchmarks import bench_kernels
    from repro.core.campaign import SweepStore, run_campaign

    store = SweepStore(args.sweeps_json)
    fig3 = run_campaign("paper-fig3", measure=args.measure)
    fig3.measured.extend(bench_kernels.campaign_records(kernel_table))
    fig5 = run_campaign("paper-fig5", measure=args.measure)
    store.put(fig3)
    store.put(fig5)
    store.save()
    _emit_campaign_table("paper-fig3", fig3)
    _emit_campaign_table("paper-fig4", fig3)
    _emit_campaign_table("paper-fig5", fig5)
    _print_crosscheck("paper-fig3", fig3)
    if args.measure:
        _print_crosscheck("paper-fig5", fig5)
    print(f"\n# wrote {store.path} ({', '.join(store.names())})")

    results = os.path.join(os.path.dirname(__file__), "../results/dryrun")
    if os.path.isdir(results) and any(f.endswith(".json") for f in os.listdir(results)):
        from benchmarks import bench_roofline

        print("\n# table: roofline (single-pod dry-run derived)")
        bench_roofline.main()
    else:
        print("\n# roofline: no dry-run artifacts under results/dryrun "
              "(run python -m repro.launch.dryrun --all first)")


if __name__ == "__main__":
    main()
