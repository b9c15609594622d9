"""Run one benchmark cell once, on the accelerator this process finds.

    python bench/run.py --workload rubicall.bulk --seed 7 --seconds 10 \
        --trace 0

The cell's entry in ``BENCHMARK.json`` names its configuration
(``bench/configs/<config>.json``, whose ``arch`` names its block
structure ``bench/arch/<arch>.py``) and traffic mix
(``bench/traffic/<traffic>.json``, whose ``kind`` names the generator
module in ``bench/traffic/``); its per-layer metrics are readers in
``bench/metrics/<name>.py``. The run makes the weights and the traffic
from ``--seed``, builds the engine through ``api.make_serving_engine``,
warms its one tick program, drives ``ServingEngine.step()`` for
``--seconds``, and then checks the served bases (and read-until
verdicts) against the plain float32 reference (``bench/check.py``).

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` traces
the window with the profiler and prints its per-layer metrics, the
device's busy and window seconds and a breakdown. The last line of
standard output is one JSON object; the numbers compared, each beside
its limit, are the last lines of standard error. Without a TPU, or with
fewer chips than the cell asks for, the run exits non-zero and prints
no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
WORK = ROOT / ".bench_work"
MIXES = BENCH / "traffic"


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find(entries, name: str, what: str):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def cell_metrics(entries, workload: str):
    return [m for m in entries if workload in m.get("workloads", [workload])]


def program_config(rc):
    """The program's registered configuration, run as ``rc`` states it:
    activations in the compute dtype, the file's weight bit-widths. A
    field that the block structure's ``program_keys`` gives another
    value is an error."""
    import dataclasses
    from bench import reference
    from repro.config import get_config
    cfg = get_config(rc["registry"])
    q = rc["quant"]
    quant = dataclasses.replace(
        cfg.quant, weight_bits=q["weight_bits"], act_bits=q["act_bits"],
        overrides=tuple((p, (w, q["act_bits"])) for p, w in q["overrides"]))
    cfg = dataclasses.replace(cfg, quant=quant, dtype=rc["dtype"])
    for k, v in reference.arch(rc).program_keys(rc).items():
        if getattr(cfg, k) != v:
            raise SystemExit(f"program config {rc['registry']!r} has {k}="
                             f"{getattr(cfg, k)!r}, the cell's file {v!r}")
    return cfg


def seed_key(seed: int):
    import jax
    import numpy as np
    return jax.random.key(int(np.random.SeedSequence(seed).generate_state(
        1)[0]))


def calibration_signal(seed: int, n: int = 4, samples: int = 16384):
    """(n, samples, 1) simulated squiggle for the BatchNorm statistics."""
    import numpy as np
    from bench.traffic import squiggle
    rng = np.random.default_rng([seed, 4])
    table = squiggle.pore_table()
    reads = []
    while len(reads) < n:
        s = squiggle.read_signal(rng, table, samples // 6)
        if s.shape[0] >= samples:
            reads.append(s[:samples])
    return np.stack(reads)[..., None]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = find(bench["workloads"], args.workload, "workload")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < int(wl["chips"]):
        print(f"needs {wl['chips']} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s)", file=sys.stderr)
        return 3
    result, _ = run(args, bench)
    sys.stdout.flush()
    print(json.dumps(result))
    return 0


def run(args, bench):
    """One run of a cell on the devices JAX holds; prints the compared
    numbers to standard error and returns the result line's object and
    what the check read: the float32 ``reference``, the ``sample`` of
    served reads it compared and the run's ``info``."""
    import jax
    wl = find(bench["workloads"], args.workload, "workload")
    cfg_entry = find(bench["configs"], wl["config"], "config")
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}

    from repro.launch import compile_cache
    if compile_cache.enable_compile_cache():
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    from bench import check, peaks
    from bench import reference as ref_mod
    from bench.spans import Spans, instrument
    from repro.models import api

    rc = ref_mod.load_config(ROOT / cfg_entry["file"])
    cfg = program_config(rc)
    mix = json.loads((MIXES / f"{wl['traffic']}.json").read_text())
    gen = load_module(BENCH / "traffic" / f"{mix['kind']}.py",
                      f"bench_traffic_{mix['kind']}")
    stride = ref_mod.total_stride(rc)
    halo = -(-ref_mod.receptive_field(rc) // stride) * stride
    core = -(-int(mix["chunk_samples"]) // stride) * stride
    geometry = {"core": core, "halo": halo, "stride": stride}
    traffic = gen.make(mix, args.seed, args.seconds, geometry)

    key = seed_key(args.seed)
    params, state = jax.jit(lambda k, c: ref_mod.make_weights(k, rc, c))(
        key, calibration_signal(args.seed))
    jax.block_until_ready((params, state))
    kw = {}
    cls_params = None
    if traffic.eject_after_chunks:
        from repro.serving.stream import ReadUntil
        # fixed, not drawn from the seed: the runner bakes the head's
        # weights into its compiled tick as constants, so a head per seed
        # would miss the compile cache on every run
        cls_params = jax.jit(ref_mod.classifier_weights)(jax.random.key(0))
        kw["read_until"] = ReadUntil(
            params=cls_params, eject_after_chunks=traffic.eject_after_chunks,
            threshold=check.THRESHOLD)
    engine = api.make_serving_engine(
        params, cfg, n_slots=traffic.n_slots,
        chunk_samples=traffic.chunk_samples, model_state=state, **kw)
    t = time.perf_counter()
    engine.warmup()
    warmup_s = time.perf_counter() - t

    spans = Spans(annotate=bool(args.trace))
    instrument(engine, spans, stride)
    traffic.prepare(engine)
    trace_dir = WORK / f"trace-{args.workload}-{args.seed}"
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)

    clock = time.perf_counter
    spans.on = True
    t0 = clock()
    setup_s = t0 - T_START
    with spans.span("window"):
        while True:
            now = clock() - t0
            if now >= args.seconds:
                break
            with spans.span("append"):
                traffic.feed(engine, now)
            ticks = spans.counters["ticks"]
            engine.step()
            traffic.after_step(engine, clock() - t0)
            if spans.counters["ticks"] == ticks:
                due = traffic.next_due(now)
                if due is not None:
                    wait = min(due, args.seconds) - (clock() - t0)
                    if wait > 0:
                        with spans.span("wait"):
                            time.sleep(wait)
    t_end = clock() - t0
    spans.on = False
    if args.trace:
        jax.profiler.stop_trace()

    e2e = traffic.close(engine, t_end)
    e2e["samples_per_s"] = spans.counters["samples"] / t_end
    e2e["setup_s"] = setup_s
    stats = devs[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    del engine, kw
    gc.collect()

    t_check = time.perf_counter()
    reference = ref_mod.Reference(rc, params, state)
    verdict = check.compare(reference, traffic.served(), traffic.judged(),
                            args.seed, cls_params=cls_params, core=core,
                            halo=halo, k=traffic.eject_after_chunks)

    check_s = time.perf_counter() - t_check

    units = {m["name"]: m["unit"] for m in bench["end_to_end"]
             + bench["per_layer"]}
    metrics = {}
    if args.trace:
        from bench import trace as trace_mod
        red = trace_mod.reduce_dir(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        ctx = {"spans": spans, "counters": spans.counters, "trace": red,
               "e2e": e2e, "cfg": rc, "n_slots": traffic.n_slots,
               "window_s": t_end, "warmup_s": warmup_s,
               "peaks": peaks.peaks(device["kind"]),
               "peak_flops": peaks.compute_peak(device["kind"], rc["dtype"])}
        for m in cell_metrics(bench["per_layer"], args.workload):
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 "bench_metric_" + m["name"].replace(".", "_"))
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": units[m["name"]]}
    else:
        for m in cell_metrics(bench["end_to_end"], args.workload):
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": units[m["name"]]}

    checks = verdict["checks"]
    info = dict(verdict["info"], ticks=spans.counters["ticks"],
                window_s=t_end, warmup_s=warmup_s, check_s=check_s,
                events=getattr(traffic, "n_events", None))
    print(f"[bench] {args.workload} seed {args.seed}: {json.dumps(info)}",
          file=sys.stderr)
    for name, c in checks.items():
        print(f"[bench] {name} = {c['value']!r} ({c['op']} {c['limit']})",
              file=sys.stderr)
    result = {"correct": verdict["correct"],
              "attempted": int(traffic.attempted),
              "failed": int(traffic.failed),
              "metrics": metrics, "device": device}
    if args.trace:
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["checks"] = checks
    return result, {"reference": reference, "sample": verdict["sample"],
                    "info": info}


if __name__ == "__main__":
    sys.exit(main())
