"""The control of ``correct``, read on the chip at a cell's own size.

    python bench/control.py --workload rubicall.bulk --seeds 101 112 \
        --seconds 4 --precision float8_e4m3fn

For each seed, one run of the cell (a short window at the cell's own
load, through the same code as a benchmark run) gives the program's
numbers compared. Then the reference forward, computed in each
``--precision``, is put in the program's place: it basecalls the same
sampled reads (whole read, greedy CTC) and ``bench/check.py``'s
comparison judges its bases as it judges the served ones; it has to
come out not correct. One JSON line per seed. The limits in
``bench/check.py`` are set from these readings (``PERF.md``).
Benchmark runs never run this.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1]),
                str(Path(__file__).resolve().parents[1] / "src")]

from bench import check  # noqa: E402
from bench import reference as ref_mod  # noqa: E402
from bench import run as bench_run  # noqa: E402


def controls(checked, seed: int, precisions) -> dict:
    """``compare``'s verdict on the control's bases, per precision."""
    ref = checked["reference"]
    out = {}
    for p in precisions:
        ctl = ref_mod.Reference(ref.cfg, ref.params, ref.state, precision=p)
        v = check.compare(ref, check.control_served(ctl, checked["sample"]),
                          [], seed)
        out[p] = {"correct": v["correct"], "checks": v["checks"]}
    return out


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs=2, required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--precision", nargs="+",
                    default=["float8_e4m3fn"])
    a = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 3
    bench = json.loads((bench_run.ROOT / "BENCHMARK.json").read_text())
    for seed in range(a.seeds[0], a.seeds[1] + 1):
        t0 = time.perf_counter()
        args = bench_run.parse(["--workload", a.workload, "--seed",
                                str(seed), "--seconds", str(a.seconds)])
        res, checked = bench_run.run(args, bench)
        t1 = time.perf_counter()
        ctl = controls(checked, seed, a.precision)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "checks": res["checks"], "info": checked["info"],
                          "control": ctl, "metrics": res["metrics"],
                          "run_s": t1 - t0,
                          "control_s": time.perf_counter() - t1}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
