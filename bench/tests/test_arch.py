"""Block structures as files of their own (``bench/arch/<arch>.py``),
found by the configuration's ``arch`` (CPU only).

The separable-conv module reproduces, bit for bit, the weights,
BatchNorm state and reference log-probs that the harness made before
block structures moved out of ``bench/reference.py`` (digests recorded
on the CPU at commit dd453bd), and the same operation and byte counts.
A structure that only the tests' data holds (full convs, a head with a
bias) runs through the harness with no edit to it.
"""
import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import flops, reference
from bench import run as bench_run
from bench.traffic import squiggle

DATA = Path(__file__).with_name("data")
SEED = 2 ** 31 + 977


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _weights_and_read(cfg):
    params, state = jax.jit(lambda k, c: reference.make_weights(k, cfg, c))(
        bench_run.seed_key(SEED), bench_run.calibration_signal(SEED))
    read = squiggle.read_signal(np.random.default_rng(SEED),
                                squiggle.pore_table(), 900)
    return params, state, read


@pytest.mark.parametrize("config,weights,log_probs", [
    ("rubicall_smoke",
     "cd28ce69606924c868e55d9a2ea5a3c134d100c84958c288ea8fdfb0bdd997f2",
     "e1df2b50a0de0d36aee3e882c414cf708de66680fb1ffb64f53c0d4990927927"),
    ("bonito_smoke",
     "44dd2f0562b37c0893093b06a5a17788b5ca2689e373ff0525b3a16cc047512a",
     "b40ed961b33c428f15188a9a66f2deaa44ebfa72ea3b1e3e40ae4ff73bdfc2f0"),
])
def test_sepconv_matches_parent_bit_for_bit(config, weights, log_probs):
    cfg = reference.load_config(DATA / f"{config}.json")
    params, state, read = _weights_and_read(cfg)
    assert _digest((params, state)) == weights
    lp = reference.Reference(cfg, params, state).log_probs(read)
    assert lp.shape == (2705, 5)
    assert _digest(lp) == log_probs


def test_rubicall_counts_match_parent():
    cfg = reference.load_config(bench_run.ROOT / "bench/configs/rubicall.json")
    assert flops.flops_per_frame(cfg) == 7153154.0
    assert flops.weight_bytes(cfg) == 7307266.0
    assert reference.total_stride(cfg) == 3
    assert reference.receptive_field(cfg) == 3237


# ------------------------------------------- a structure in the tests' data


@pytest.fixture
def fullconv(monkeypatch):
    monkeypatch.setattr(reference, "ARCHS", DATA / "arch")
    return reference.load_config(DATA / "fullconv_smoke.json")


def test_new_structure_counts_by_hand(fullconv):
    # blocks 2 x 9 x 1 x 8 and 2 x 5 x 8 x 12, head 2 x 12 x 5 + 5
    assert flops.flops_per_frame(fullconv) == 144 + 960 + 125
    # bf16 convs (72 + 480 + 60 weights), f32 head bias (5) and BatchNorm
    # (4 statistics x 20 channels)
    assert flops.weight_bytes(fullconv) == 612 * 2 + 5 * 4 + 80 * 4
    assert reference.total_stride(fullconv) == 3
    assert reference.receptive_field(fullconv) == 1 + 8 + 4 * 3
    work = flops.tick_work(fullconv, frames=10, samples=30)
    assert work["flops"] == 10 * 1229
    assert work["bytes"] == 1564 + 4 * 30 + 4 * 5 * 10


def _numpy_log_probs(cfg, params, state, read):
    """The structure's forward over one unpadded read in float64."""
    f64 = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64),
                                 (params, state))
    params, state = f64
    x = np.asarray(read, np.float64)[:, None]
    for i, stride in enumerate(cfg["strides"]):
        p, st = params[f"block{i:02d}"], state[f"block{i:02d}"]
        w = p["conv"]
        k = w.shape[0]
        xp = np.pad(x, (((k - 1) // 2, k - 1 - (k - 1) // 2), (0, 0)))
        n_out = -(-x.shape[0] // stride)
        h = np.stack([np.einsum("kc,kcd->d", xp[t * stride:t * stride + k], w)
                      for t in range(n_out)])
        h = (h - st["mean"]) / np.sqrt(st["var"] + 1e-3) * p["bn"]["scale"] \
            + p["bn"]["bias"]
        x = h / (1.0 + np.exp(-h))
    logits = x @ params["head_pw"][0] + params["head_b"]
    m = logits.max(axis=1, keepdims=True)
    return logits - m - np.log(np.exp(logits - m).sum(axis=1, keepdims=True))


def test_new_structure_runs_through_the_harness(fullconv):
    params, state, read = _weights_and_read(fullconv)
    assert params["block00"]["conv"].dtype == jax.numpy.bfloat16
    assert params["head_b"].dtype == jax.numpy.float32
    assert set(state["block01"]) == {"mean", "var"}
    lp = reference.Reference(fullconv, params, state).log_probs(read)
    want = _numpy_log_probs(fullconv, params, state, read)
    assert lp.shape == want.shape == (-(-read.shape[0] // 3), 5)
    np.testing.assert_allclose(lp, want, rtol=1e-4, atol=1e-4)


# ------------------------------------------------- configuration errors


@pytest.mark.parametrize("key,value", [
    ("kernel_sizes", [9, 9, 25, 27]), ("repeats", [1, 1, 1, 2]),
    ("use_skips", True), ("n_bases", 6)])
def test_program_config_names_the_key_that_differs(key, value):
    rc = reference.load_config(DATA / "rubicall_smoke.json")
    assert bench_run.program_config(rc).n_blocks == 4
    with pytest.raises(SystemExit, match=f"has {key}="):
        bench_run.program_config(dict(rc, **{key: value}))


@pytest.mark.parametrize("arch,names", [(None, "bad.json"),
                                        ("nosuch", "nosuch.py")])
def test_config_without_a_known_arch_is_an_error(tmp_path, arch, names):
    cfg = json.loads((DATA / "rubicall_smoke.json").read_text())
    del cfg["arch"]
    if arch:
        cfg["arch"] = arch
    path = tmp_path / "bad.json"
    path.write_text(json.dumps(cfg))
    with pytest.raises(ValueError, match=names):
        reference.load_config(path)
