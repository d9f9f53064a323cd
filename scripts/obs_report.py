#!/usr/bin/env python
"""Text dashboard over a trace dump (+ optional metrics snapshot).

  PYTHONPATH=src python scripts/obs_report.py obs_trace.jsonl \
      --metrics obs_metrics.json --strict

Input is the JSONL written by ``Tracer.export_jsonl`` (one span per line;
still-open spans carry ``"open": true``) and, optionally, the JSON written
by ``MetricsRegistry.dump_json``.  Renders:

* span census: counts per span name, closed request roots, open (orphan)
  spans — the trace completeness surface;
* request outcomes: ok / rejected / error roots, with rejection reasons;
* stage breakdown: mean/max duration per span name (queued,
  svc.preflight, execute, launch and the phases inside it);
* launch fan-in: group sizes carried by launch spans (requests per
  batched core call);
* metrics: every counter/gauge plus histogram p50/p95/p99 rows.

``--strict`` exits non-zero when any span is still open (an orphan: a
request that never closed its tree) — the obs-smoke CI gate.

stdlib-only on purpose: the dashboard must render on a box with no JAX.
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import Counter, defaultdict


def load_spans(path: str) -> list[dict]:
    spans = []
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if line:
                spans.append(json.loads(line))
    return spans


def _fmt_us(us: float) -> str:
    if us >= 1e6:
        return f"{us / 1e6:.2f}s"
    if us >= 1e3:
        return f"{us / 1e3:.1f}ms"
    return f"{us:.0f}us"


def render(spans: list[dict], metrics: dict | None) -> tuple[str, int]:
    """(report text, orphan count)."""
    lines: list[str] = []
    closed = [s for s in spans if not s.get("open")]
    orphans = [s for s in spans if s.get("open")]
    roots = [s for s in closed if s.get("parent_id") is None]
    request_roots = [s for s in roots if s["name"] == "request"]

    lines.append("== span census ==")
    by_name = Counter(s["name"] for s in spans)
    for name, n in by_name.most_common():
        lines.append(f"  {name:<12} {n}")
    lines.append(f"  closed request roots: {len(request_roots)}")
    lines.append(f"  open (orphan) spans:  {len(orphans)}")
    for s in orphans[:8]:
        lines.append(f"    ORPHAN {s['name']} span_id={s['span_id']} "
                     f"attrs={s.get('attrs', {})}")

    lines.append("")
    lines.append("== request outcomes ==")
    outcomes = Counter(s.get("status", "ok") for s in request_roots)
    for status, n in sorted(outcomes.items()):
        lines.append(f"  {status:<10} {n}")
    reasons = Counter(s.get("attrs", {}).get("reason")
                      for s in request_roots
                      if s.get("status") == "rejected")
    for reason, n in sorted(reasons.items(), key=lambda kv: str(kv[0])):
        lines.append(f"    rejected[{reason}]: {n}")

    lines.append("")
    lines.append("== stage breakdown (closed spans) ==")
    durs: dict[str, list[float]] = defaultdict(list)
    for s in closed:
        durs[s["name"]].append(float(s.get("duration_us") or 0.0))
    for name in sorted(durs):
        d = durs[name]
        lines.append(
            f"  {name:<12} n={len(d):<6} mean={_fmt_us(sum(d) / len(d)):<8} "
            f"max={_fmt_us(max(d))}")

    launches = [s for s in closed if s["name"] == "launch"]
    if launches:
        lines.append("")
        lines.append("== launch fan-in ==")
        sizes = [int(s.get("attrs", {}).get("group_size", len(s.get(
            "links", [])) or 1)) for s in launches]
        fanned = sum(1 for g in sizes if g > 1)
        lines.append(f"  launches: {len(launches)}  "
                     f"requests served: {sum(sizes)}  "
                     f"coalesced launches (>1 req): {fanned}  "
                     f"max group: {max(sizes)}")
        per_op = defaultdict(list)
        for s, g in zip(launches, sizes):
            per_op[s.get("attrs", {}).get("op", "?")].append(g)
        for op in sorted(per_op):
            g = per_op[op]
            lines.append(f"  {op:<10} launches={len(g):<6} "
                         f"mean group={sum(g) / len(g):.2f}")

    classes = {name: val for name, val in (metrics or {}).items()
               if name.startswith("latency_us_class_")
               and isinstance(val, dict)}
    if classes:
        # the mixed-serving split: LM token cadence vs MoE dispatch combines
        # vs plain kernel traffic, side by side on one slot loop
        lines.append("")
        lines.append("== request classes (latency_us_class_*) ==")
        total = sum(v["count"] for v in classes.values()) or 1
        for name in sorted(classes):
            val = classes[name]
            cls = name[len("latency_us_class_"):]
            lines.append(
                f"  {cls:<14} n={val['count']:<7} "
                f"share={val['count'] / total:>5.1%} "
                f"p50={_fmt_us(val['p50']):<8} "
                f"p95={_fmt_us(val['p95']):<8} "
                f"p99={_fmt_us(val['p99'])}")

    if metrics:
        lines.append("")
        lines.append("== metrics ==")
        for name in sorted(metrics):
            val = metrics[name]
            if isinstance(val, dict):          # histogram snapshot
                lines.append(
                    f"  {name:<24} n={val['count']:<7} "
                    f"p50={_fmt_us(val['p50']):<8} "
                    f"p95={_fmt_us(val['p95']):<8} "
                    f"p99={_fmt_us(val['p99'])}")
            else:
                lines.append(f"  {name:<24} {val}")

    return "\n".join(lines), len(orphans)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="span JSONL from Tracer.export_jsonl")
    ap.add_argument("--metrics", default=None,
                    help="metrics JSON from MetricsRegistry.dump_json")
    ap.add_argument("--strict", action="store_true",
                    help="exit 1 when any span is still open (orphan)")
    args = ap.parse_args(argv)

    spans = load_spans(args.trace)
    metrics = None
    if args.metrics:
        with open(args.metrics, encoding="utf-8") as fh:
            metrics = json.load(fh)
    report, orphans = render(spans, metrics)
    print(report)
    if args.strict and orphans:
        print(f"\nSTRICT: {orphans} orphan span(s) — trace is incomplete",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
