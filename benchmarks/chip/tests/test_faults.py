"""The harness without its look for a chip: a sound run is correct, and a
run whose timed path is broken underneath is not.  The faults a one-chip
serving cell on the cold searcher can have: a token altered where the
searcher produces it, and half of the slots left unsearched (it keeps no
state across tokens, and exchanges nothing between chips).  Last, the
faults the comparison cannot see, each shown passing."""
import importlib
import os
import subprocess
import sys

import jax.numpy as jnp
import pytest

import tiny
from chipbench import spec

CELL = spec.load_benchmark()["workloads"][0]["name"]


def test_sound_run_is_correct(tmp_path):
    res = tiny.run(tmp_path)
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    assert list(res)[-1] == "compared"
    for c in res["compared"].values():
        assert c["value"] <= c["limit"]


def test_traced_run_is_correct(tmp_path):
    """A traced run traces its first steps, serves the rest of the window
    and is judged like any other (the CPU has no device plane, so only
    the readers that need none report)."""
    from chipbench import cell
    res = cell.run(tiny.workload(), 5, 2.0, True, 0.0, tmp_path / "cache",
                   require_chip=False)
    assert res["correct"] and res["attempted"] > 0
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert "search_program_gb" in res["metrics"]
    assert list(res)[-1] == "compared"


class Altered:
    """The per-token searcher with each token it produces moved half the
    vocabulary away."""

    def __init__(self, searcher, vocab):
        self.searcher, self.vocab = searcher, vocab

    def __call__(self, buf, lens, rng):
        return (self.searcher(buf, lens, rng) + self.vocab // 2) % self.vocab

    def lower(self, *args):
        return self.searcher.lower(*args)


class HalfSlots(Altered):
    """The per-token searcher serving the first half of the slots, each
    other slot given the token of the slot half the batch before it."""

    def __call__(self, buf, lens, rng):
        toks = self.searcher(buf, lens, rng)
        h = (toks.shape[0] + 1) // 2
        return jnp.concatenate([toks[:h], toks[:toks.shape[0] - h]])


@pytest.mark.parametrize("fault", [Altered, HalfSlots])
def test_broken_searcher_is_not_correct(tmp_path, monkeypatch, fault):
    import repro.serving.engine as engine
    real = engine.make_batched_searcher
    monkeypatch.setattr(
        engine, "make_batched_searcher",
        lambda cfg, *a, **k: fault(real(cfg, *a, **k), cfg.vocab_size))
    res = tiny.run(tmp_path)
    assert not res["correct"] and res["failed"] > 0
    assert any(c["value"] > c["limit"] for c in res["compared"].values())


def _int8_kv(real):
    """decode_attention reading K and V rounded to int8 per position."""
    def r(x):
        s = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-30) / 127
        return jnp.round(x / s) * s
    return lambda q, k, v, valid, **kw: real(q, r(k), r(v), valid, **kw)


def _zeroed(real):
    """decode_attention returning nothing but zeros."""
    return lambda q, k, v, valid, **kw: jnp.zeros_like(
        real(q, k, v, valid, **kw))


@pytest.mark.parametrize("fault", ["int8_kv", "decode_attention_zeroed",
                                   "constant_best_action"])
def test_fault_the_comparison_cannot_see(tmp_path, monkeypatch, fault):
    """Every committed token is the root prefill's own top-A entry at the
    search's pick, so faults in the playouts' cached decode or in the pick
    itself leave it inside the reference's top-A: these runs pass.  A
    later benchmark that reads the root's visits from the program can
    turn each of these into a failing case."""
    calls = []
    if fault == "constant_best_action":
        md = importlib.import_module("repro.serving.mcts_decode")
        real = md.search_keys

        def patched(*a, **k):
            calls.append(1)
            res = real(*a, **k)
            return res._replace(best_action=jnp.zeros_like(res.best_action))
        monkeypatch.setattr(md, "search_keys", patched)
    else:
        import repro.kernels.decode_attention.ops as da
        broken = (_int8_kv if fault == "int8_kv" else _zeroed)(
            da.decode_attention)

        def patched(*a, **k):
            calls.append(1)
            return broken(*a, **k)
        monkeypatch.setattr(da, "decode_attention", patched)
    res = tiny.run(tmp_path)
    assert calls, "the fault was never planted in the timed path"
    assert res["correct"] and res["failed"] == 0


def _run(args, cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_chip():
    r = _run([str(spec.HERE / "run.py"), "--workload", CELL,
              "--seed", "1", "--seconds", "1", "--trace", "0"], spec.ROOT)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout
    assert "no accelerator" in r.stderr


def test_refuses_without_the_program(tmp_path):
    """A checkout that holds only ``BENCHMARK.json`` and the benchmark's
    own files has no program to serve: the run fails and prints nothing,
    also past the look for a chip."""
    import shutil
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    code = ("import sys; sys.path.insert(0, 'benchmarks/chip'); "
            "from tests import tiny; import pathlib; "
            "print(tiny.run(pathlib.Path('.')))")
    r = _run(["-c", code], tmp_path)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout and "'correct'" not in r.stdout
    assert "repro" in r.stderr
