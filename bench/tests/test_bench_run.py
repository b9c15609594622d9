"""Whole runs of the harness on the CPU at a size a test run can hold.

The harness's look for a chip is skipped (``run`` is called directly);
the rest of a run is driven as on the chip. A sound run comes out
``correct``; with the timed path broken underneath it does not: served
bases altered where the runner produces them, half of every tick's rows
left out, and read-until verdicts flipped. The control (the reference
in float8 in the program's place, its bases judged by the same
comparison) must come out not correct where sound bf16 runs hold.
"""
import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

from bench import control
from bench import run as bench_run

DATA = Path(__file__).with_name("data")


@pytest.fixture(autouse=True)
def _test_mixes_no_compile_cache(monkeypatch):
    """Traffic from the tests' data; no persistent compile cache, which
    would outlive the test in the worker's process."""
    from repro.launch import compile_cache
    monkeypatch.setattr(bench_run, "MIXES", DATA)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: None)


def _bench(config: str, mix: str):
    bench = json.loads((bench_run.ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": config,
                         "file": f"bench/tests/data/{config}.json"}]
    bench["workloads"] = [{"name": "t", "config": config, "traffic": mix,
                           "chips": 1}]
    for m in bench["end_to_end"]:
        m.pop("workloads", None)
    return bench


def _run(config="rubicall_smoke", mix="bulk_tiny", seed=2 ** 31 + 11,
         seconds=3.0):
    args = bench_run.parse(["--workload", "t", "--seed", str(seed),
                            "--seconds", str(seconds)])
    return bench_run.run(args, _bench(config, mix))


@pytest.mark.parametrize("config,mix", [("rubicall_smoke", "bulk_tiny"),
                                        ("bonito_smoke", "bulk_tiny"),
                                        ("rubicall_smoke", "live_tiny")])
def test_sound_run_is_correct(config, mix):
    res, _ = _run(config, mix)
    assert res["correct"], res["checks"]
    assert res["checks"]["gap_mean"]["value"] == 0.0     # float32 path
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    assert "setup_s" in res["metrics"]
    if mix == "live_tiny":
        assert res["checks"]["verdicts_judged"]["value"] >= 1
        assert {"eject_p95_ms", "read_lag_p95_ms"} <= set(res["metrics"])
    else:
        assert res["metrics"]["samples_per_s"]["value"] > 0
    assert list(res)[-1] == "checks"


def _alter_tokens(monkeypatch):
    from repro.serving.runner import BasecallerRunner
    collect = BasecallerRunner.collect

    def altered(self, handle, discard=frozenset()):
        return [[1 + t % 4 for t in toks] for toks in
                collect(self, handle, discard=discard)]
    monkeypatch.setattr(BasecallerRunner, "collect", altered)


def _drop_half_batch(monkeypatch):
    from repro.serving.runner import BasecallerRunner
    dispatch = BasecallerRunner.dispatch

    def half(self, works):
        kept = list(works)
        for i in range(self.n_slots // 2, self.n_slots):
            w = kept[i]
            if w is not None:
                p = w.payload
                kept[i] = w._replace(payload=(np.zeros_like(p[0]),) + p[1:])
        return dispatch(self, kept)
    monkeypatch.setattr(BasecallerRunner, "dispatch", half)


def _flip_verdicts(monkeypatch):
    from repro.models.basecaller import classifier
    forward = classifier.forward
    monkeypatch.setattr(classifier, "forward",
                        lambda p, w: -forward(p, w))


@pytest.mark.parametrize("fault,mix", [(_alter_tokens, "bulk_tiny"),
                                       (_drop_half_batch, "bulk_tiny"),
                                       (_flip_verdicts, "live_tiny")])
def test_broken_timed_path_is_not_correct(monkeypatch, fault, mix):
    fault(monkeypatch)
    res, _ = _run(mix=mix)
    assert not res["correct"], res["checks"]


def test_control_fails_where_sound_bf16_holds(monkeypatch):
    """At RUBICALL's full depth (28 blocks, 32 channels here), bf16
    serving holds and the float8 control, judged by the same
    comparison, does not."""
    from bench import reference

    rc = reference.load_config(bench_run.ROOT / "bench/configs/rubicall.json")
    narrow = dict(rc, channels=[32] * len(rc["channels"]))
    monkeypatch.setattr(reference, "load_config",
                        lambda path: dict(narrow))
    program_config = bench_run.program_config

    def narrow_program(cfg):
        base = program_config(dict(cfg, channels=rc["channels"]))
        return dataclasses.replace(base, channels=tuple(cfg["channels"]),
                                   d_model=32)
    monkeypatch.setattr(bench_run, "program_config", narrow_program)
    # reads of at most three windows, so that some finish in the window
    # even on a loaded host
    seed = 2 ** 31 + 11
    res, checked = _run(mix="bulk_short", seed=seed, seconds=6.0)
    assert res["correct"], res["checks"]
    ctl = control.controls(checked, seed, ["float8_e4m3fn"])
    assert not ctl["float8_e4m3fn"]["correct"], ctl


def test_main_refuses_a_cpu(capsys):
    rc = bench_run.main(["--workload", "rubicall.bulk", "--seed", "1",
                         "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == "" and "TPU" in out.err
