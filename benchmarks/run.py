"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. Figure mapping:
  bench_quant       -> Fig. 7 (quant accuracy), Fig. 8 (quant size)
  bench_pruning     -> Fig. 6 (Bonito), Fig. 14 (RUBICALL)
  bench_skipclip    -> Fig. 13 (+ Supplementary S1)
  bench_throughput  -> Fig. 9/10 + Table S1 (v5e roofline projection)
  bench_roofline    -> EXPERIMENTS.md §Roofline table (dry-run artifacts)
  bench_serving     -> continuous batching vs static batch (ROADMAP
                       "heavy traffic" axis; not a paper figure)
"""
import sys
import traceback


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.2f},{derived}")


def main() -> int:
    """Run every module (or the one named); exit status 1 when any
    module failed — a ``__FAILED`` row alone would pass in CI."""
    only = sys.argv[1] if len(sys.argv) > 1 else ""
    from benchmarks import (bench_pruning, bench_quant, bench_roofline,
                            bench_serving, bench_skipclip, bench_throughput)
    mods = {
        "quant": bench_quant, "pruning": bench_pruning,
        "skipclip": bench_skipclip, "throughput": bench_throughput,
        "roofline": bench_roofline, "serving": bench_serving,
    }
    failed = []
    for name, mod in mods.items():
        if only and only != name:
            continue
        try:
            mod.run(emit)
        except Exception as e:
            emit(f"{name}__FAILED", 0.0, f"{type(e).__name__}:{e}")
            traceback.print_exc()
            failed.append(name)
    return 1 if failed else 0


if __name__ == '__main__':
    sys.exit(main())
