"""Benchmark harness: one module per paper table + microbenchmarks.

Prints ``name,value,derived`` CSV rows (value is the table's primary
quantity: mm^2/mW for Table 1, ms for Tables 2-3, FPS for Table 4, AP for
Table 5, cycles/us for micro, seconds for roofline, windows/sec for the
multi-stream Tables 6-7). ``--json PATH`` additionally writes the whole
suite as one JSON document: ``{suite: {"rows": [[name, value, derived],
...], "seconds": s, "ok": bool}}`` — the machine-readable artifact CI and
dashboards diff across commits. Suites instrumented with ``repro.obs``
(table7, table8, chaos, micro) additionally carry a ``"metrics"`` key:
the registry snapshot of the run's serving traffic (see
``docs/observability.md``).

The document also carries a top-level ``"meta"`` key (git SHA, UTC
timestamp, JAX backend, argv) so ``benchmarks/trend.py`` can append the
run to the perf-trend history and gate regressions against the rolling
baseline — workflow in ``docs/observability.md``.
"""
from __future__ import annotations

import argparse
import datetime
import json
import os
import subprocess
import sys
import time
import traceback


def run_meta() -> dict:
    """Provenance stamp for a benchmark artifact: git SHA (``GITHUB_SHA``
    or ``git rev-parse``), UTC timestamp, JAX backend, argv."""
    sha = os.environ.get("GITHUB_SHA", "")
    if not sha:
        try:
            sha = subprocess.run(
                ["git", "rev-parse", "HEAD"], capture_output=True,
                text=True, timeout=10,
                cwd=os.path.dirname(os.path.abspath(__file__)),
            ).stdout.strip()
        except (OSError, subprocess.SubprocessError):
            sha = ""
    try:
        import jax
        backend = jax.default_backend()
    except Exception:  # noqa: BLE001 — provenance must never fail the run
        backend = "unknown"
    return {
        "sha": sha or "unknown",
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
        "backend": backend,
        "argv": list(sys.argv[1:]),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="", metavar="PATH",
                    help="also write results as JSON to PATH")
    ap.add_argument("--only", default="", metavar="NAMES",
                    help="run a comma-separated subset of suites "
                         "(e.g. table7,table8)")
    args = ap.parse_args()

    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    from . import (autotune_blocks, chaos_recovery, loadgen, micro_aligner,
                   roofline_summary, table1_hw, table2_envelope,
                   table3_runtime, table4_throughput, table5_accuracy,
                   table6_multistream, table7_async, table8_pareto,
                   torr_reuse_ablation)

    suites = [
        ("table1", table1_hw),
        ("table2", table2_envelope),
        ("table3", table3_runtime),
        ("table4", table4_throughput),
        ("table5", table5_accuracy),
        ("table6", table6_multistream),
        ("table7", table7_async),
        ("table8", table8_pareto),
        ("torr_ablation", torr_reuse_ablation),
        ("chaos", chaos_recovery),
        ("loadgen", loadgen),
        ("micro", micro_aligner),
        ("autotune", autotune_blocks),
        ("roofline", roofline_summary),
    ]
    if args.only:
        names = [n.strip() for n in args.only.split(",") if n.strip()]
        valid = [n for n, _ in suites]
        unknown = set(names) - set(valid)
        if unknown:
            print(f"unknown suite(s) {sorted(unknown)}; "
                  f"valid suites: {', '.join(valid)}", file=sys.stderr)
            sys.exit(2)
        suites = [(n, m) for n, m in suites if n in names]
    failed = []
    report = {"meta": run_meta()}

    def _write_report() -> None:
        if args.json:
            with open(args.json, "w") as f:
                json.dump(report, f, indent=1, sort_keys=True)
            print(f"wrote {args.json}", file=sys.stderr)

    print("name,value,derived")
    try:
        for name, mod in suites:
            t0 = time.time()
            rows = []
            error = None
            try:
                for row in mod.run():
                    rows.append(row)
                    print(",".join(str(x) for x in row), flush=True)
                ok = True
                print(f"{name}/_suite_seconds,{time.time()-t0:.1f},ok",
                      flush=True)
            except Exception:  # noqa: BLE001
                ok = False
                error = traceback.format_exc()
                failed.append(name)
                traceback.print_exc()
                print(f"{name}/_suite_seconds,{time.time()-t0:.1f},FAILED",
                      flush=True)
            report[name] = {"rows": [list(r) for r in rows],
                            "seconds": round(time.time() - t0, 1), "ok": ok}
            if error is not None:
                # keep the partial rows AND the cause: a suite that dies
                # mid-run still contributes everything it measured
                report[name]["error"] = error
            # suites instrumented with repro.obs (table7/table8/micro)
            # expose their registry snapshot for the artifact; a snapshot
            # crash must not discard the suite's rows
            snap_fn = getattr(mod, "metrics_snapshot", None)
            if snap_fn is not None:
                try:
                    snap = snap_fn()
                except Exception:  # noqa: BLE001
                    report[name].setdefault(
                        "error", traceback.format_exc())
                else:
                    if snap is not None:
                        report[name]["metrics"] = snap
    except BaseException:
        # KeyboardInterrupt / SystemExit / MemoryError mid-run: the JSON
        # still lands with every completed suite's rows and an "error"
        # marker instead of being discarded wholesale
        report["error"] = traceback.format_exc()
        _write_report()
        raise
    _write_report()
    if failed:
        print(f"FAILED suites: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
