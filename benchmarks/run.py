"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  Roofline/dry-run tables live in
``benchmarks.roofline`` (they read the dry-run JSON artifacts).  The static
analyzer's cost is tracked alongside the perf benches: ``bench_analysis``
times each pass and writes ``BENCH_analysis.json`` so a slow rule shows up
the same way a slow kernel does.
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

from repro.launch.compile_cache import use_compile_cache


def bench_analysis(out_path: str | Path = "BENCH_analysis.json") -> dict:
    """Time the full analyzer suite; write wall-time + per-pass finding
    counts to ``out_path`` and return the document."""
    from repro.analysis import run_all

    findings, counts, elapsed = run_all()
    doc = {
        "wall_s": round(sum(elapsed.values()), 3),
        "per_pass_seconds": {k: round(v, 3) for k, v in elapsed.items()},
        "per_pass_findings": counts,
        "errors": sum(f.severity == "ERROR" for f in findings),
        "warnings": sum(f.severity == "WARNING" for f in findings),
    }
    Path(out_path).write_text(json.dumps(doc, indent=2, sort_keys=True))
    return doc


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--analysis-out", default="BENCH_analysis.json",
                    help="where bench_analysis writes its JSON")
    args = ap.parse_args()
    use_compile_cache()

    from benchmarks.kernels_bench import bench_kernels
    from benchmarks.paper import ALL_BENCHES

    print("name,us_per_call,derived")
    for bench in ALL_BENCHES:
        for name, us, derived in bench():
            print(f"{name},{us:.1f},{derived}")
    for name, us, derived in bench_kernels():
        print(f"{name},{us:.1f},{derived}")
    doc = bench_analysis(args.analysis_out)
    for pass_name, secs in sorted(doc["per_pass_seconds"].items()):
        n = doc["per_pass_findings"][pass_name]
        print(f"analysis_{pass_name},{secs * 1e6:.1f},findings={n}")


if __name__ == "__main__":
    main()
