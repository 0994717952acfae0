#!/usr/bin/env python3
"""Bring-up smoke test of MCTS serving on a TPU.

Drives ``ServingEngine(decode="mcts")`` end to end on smollm-135m at its
published widths (30 layers, d_model 576, 9 heads / 3 KV heads, d_ff 1536,
vocab 49152, bf16; random weights from ``--seed``), checks what comes out,
and proves that the compiled per-token program runs the Pallas kernels.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the four-chip phase only (one host)

One process drives every chip: nothing here starts a child process.  When
JAX finds no TPU the script exits non-zero without a result line.  A passing
run ends with one JSON line,

    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

Every time printed is a smoke timing of this one run, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "smollm-135m"
MAX_BATCH, MAX_SEQ = 4, 256
N_REQUESTS, MAX_NEW = 8, 8
PROMPT_LENS = (32, 128)
# bf16 keeps 8 significant bits: |kernel - f32 reference| may be at most
# ATTN_TOL * (1 + |reference|), i.e. two to four bf16 ulps at any magnitude
ATTN_TOL = 2.0 ** -6


def log(msg: str) -> None:
    print(msg, flush=True)


def device_facts(jax):
    """The device JAX reports; exits non-zero unless it is a TPU."""
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {len(devs)} "
                 f"{d.platform} device(s)")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def model(seed: int):
    import jax
    from repro.configs import get_config
    from repro.models.base import count_params, get_family
    cfg = get_config(ARCH)
    params = get_family(cfg).init(cfg, jax.random.key(seed))
    log(f"model {cfg.name}: layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} dtype={cfg.dtype} "
        f"params={count_params(params):,} pallas={cfg.pallas}")
    return cfg, params


def peak_bytes(jax) -> int:
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def custom_calls(hlo: str):
    """Count ``tpu_custom_call`` ops in compiled HLO text, by kernel name
    (each ``pallas_call`` is named, and the name reaches the op)."""
    names = ("search_wave", "decode_attention", "flash_attention",
             "uct_select")
    lines = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    counts = {n: sum(n in ln for ln in lines) for n in names}
    counts["total"] = len(lines)
    return counts


def step_program(jax, eng, seed: int, label: str, kernels: bool):
    """No hidden fallback: lower and compile the engine's own per-token
    program (cold ``BatchedSearcher`` or stateful ``ReusableSearcher``) and
    count its custom calls.  ``kernels`` asserts the megakernel and
    ``decode_attention`` are in it, which needs the TPU's compiler."""
    import jax.numpy as jnp
    sp = eng.ecfg.mcts.search_config().params
    args = (jnp.asarray(eng.prefix_buf), jnp.asarray(eng.prefix_len),
            jax.random.key(seed))
    if eng._carry is not None:
        args += (eng._carry,)
    t0 = time.perf_counter()
    compiled = eng._mcts_search.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    counts = custom_calls(compiled.as_text())
    mem = compiled.memory_analysis()
    log(f"per-token step {label} ({type(eng._mcts_search).__name__}; "
        f"kernels={sp.resolved_kernels} wave_select="
        f"{sp.resolved_wave_select} uct_kernels={sp.resolved_uct_kernels} "
        f"model_pallas={eng.cfg.pallas}): compile {compile_s:.1f} s (cold "
        f"unless the compile cache held it); tpu_custom_call {counts}; "
        f"argument bytes {mem.argument_size_in_bytes:,}, "
        f"temp bytes {mem.temp_size_in_bytes:,}")
    if kernels:
        assert counts["search_wave"] > 0, f"megakernel missing: {counts}"
        assert counts["decode_attention"] > 0, (
            f"decode kernel missing: {counts}")
    return counts


def serve(jax, cfg, params, dcfg, seed: int, label: str,
          kernels: bool = True):
    """Compile the engine's per-token program (``step_program``), submit
    N_REQUESTS seeded requests, drain the engine, check them.  With several
    devices the engine shards its slots over all of them."""
    import numpy as np
    from repro.serving.engine import EngineConfig, Request, ServingEngine
    eng = ServingEngine(cfg, params, EngineConfig(
        max_batch=MAX_BATCH, max_seq=MAX_SEQ, decode="mcts", mcts=dcfg))
    step_program(jax, eng, seed, label, kernels)
    rng = np.random.default_rng(seed)
    reqs = []
    for uid in range(N_REQUESTS):
        plen = int(rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1))
        reqs.append(Request(uid=uid, max_new_tokens=MAX_NEW,
                            prompt=rng.integers(1, cfg.vocab_size,
                                                size=plen)))
        eng.submit(reqs[-1])
    step_s = []
    engine_step = eng.step

    def timed_step():
        t0 = time.perf_counter()
        emitted = engine_step()
        # the step's tokens came back to the host; wait for what it left
        # queued on the device too (slot admissions, the carry)
        jax.block_until_ready(eng._carry)
        step_s.append(time.perf_counter() - t0)
        return emitted

    eng.step = timed_step
    out = eng.run_until_drained()
    toks = [t for r in reqs for t in r.out_tokens]
    done = sum(r.done for r in reqs)
    in_vocab = all(0 <= t < cfg.vocab_size for t in toks)
    warm = step_s[1:-1] or step_s      # the last step finds nothing to do
    log(f"serving {label}: {done}/{N_REQUESTS} requests done, "
        f"{len(toks)} tokens (engine counted {out['tokens']}) in "
        f"{out['steps']} steps, all in [0, {cfg.vocab_size}): {in_vocab}; "
        f"first step {step_s[0]:.2f} s (compile + run); smoke timing, not "
        f"a benchmark: median warm engine step {statistics.median(warm):.4f} "
        f"s over {len(warm)} steps; peak device bytes {peak_bytes(jax):,}")
    assert done == N_REQUESTS, f"{label}: {done}/{N_REQUESTS} done"
    assert len(toks) == N_REQUESTS * MAX_NEW == out["tokens"], (
        f"{label}: {len(toks)} tokens, engine counted {out['tokens']}")
    assert in_vocab, f"{label}: token outside [0, {cfg.vocab_size})"
    return toks


def search_parity(jax, seed: int):
    """DESIGN.md §14: mega is bit-for-bit lockstep at lanes 1; at lanes 8
    both complete exactly the budget."""
    import numpy as np
    from repro.core.domains.pgame import PGameDomain
    from repro.search import SearchConfig, SearchParams, search
    dom = PGameDomain(num_actions=4, game_depth=8, binary_reward=False,
                      seed=seed)
    key = jax.random.key(seed)

    def run(ws, lanes):
        cfg = SearchConfig(method="pipeline", budget=256, lanes=lanes,
                           wave_select=ws,
                           params=SearchParams(cp=0.7, max_depth=8))
        res = jax.jit(lambda k: search(dom, cfg, k))(key)
        return (np.asarray(res.action_visits),
                int(res.stats["playouts_completed"]),
                cfg.params.resolved_kernels)

    mega, _, kern = run("mega", 1)
    lock, _, _ = run("lockstep", 1)
    log(f"parity P-game pipeline budget 256 lanes 1 (kernels={kern}): "
        f"mega {mega.tolist()} lockstep {lock.tolist()}")
    assert np.array_equal(mega, lock), "mega != lockstep at lanes 1"
    for ws in ("mega", "lockstep"):
        visits, done, _ = run(ws, 8)
        log(f"parity lanes 8 {ws}: playouts {done}, root visits "
            f"{visits.tolist()} (sum {int(visits.sum())})")
        assert done == 256, f"{ws} lanes 8 completed {done} of 256"


def _attn_err(got, want):
    """Largest |got - want| in units of ATTN_TOL * (1 + |want|)."""
    import jax.numpy as jnp
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want)
                         / (ATTN_TOL * (1.0 + jnp.abs(want)))))


def attention_parity(jax, cfg, seed: int, interpret: bool = False):
    """The Pallas attention kernels against their jnp references at the
    smoke's shapes: a cache row of MAX_SEQ + depth + rollout positions.
    ``interpret`` runs the kernels in the Pallas interpreter (CPU)."""
    import numpy as np
    from repro.kernels.decode_attention import ops as da
    from repro.kernels.flash_attention import ops as fa
    from repro.serving.mcts_decode import MCTSDecodeConfig
    d = MCTSDecodeConfig()
    sk = MAX_SEQ + d.search_depth + d.rollout_len
    h, hkv, hd, b = cfg.n_heads, cfg.kv_heads, cfg.head_dim, 16
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (b, 1, h, hd), cfg.jdtype)
    k = jax.random.normal(ks[1], (b, sk, hkv, hd), cfg.jdtype)
    v = jax.random.normal(ks[2], (b, sk, hkv, hd), cfg.jdtype)
    valid = jax.random.randint(ks[3], (b,), 1, sk + 1)
    got = da.decode_attention(q, k, v, valid, interpret=interpret)
    with jax.default_matmul_precision("highest"):   # an f32 reference
        want = da.decode_attention(q, k, v, valid, use_ref=True)
    err = _attn_err(got, want)
    log(f"parity decode_attention [B={b}, Sk={sk}, H={h}/{hkv}, D={hd}] "
        f"{cfg.dtype}: max err {err:.3f} of the bf16 tolerance "
        f"{ATTN_TOL:.3e} * (1 + |ref|)")
    assert np.isfinite(err) and err <= 1.0, err
    qs = jax.random.normal(ks[0], (1, sk, h, hd), cfg.jdtype)
    got = fa.flash_attention(qs, k[:1], v[:1], causal=True,
                             interpret=interpret)
    with jax.default_matmul_precision("highest"):
        want = fa.flash_attention(qs, k[:1], v[:1], causal=True,
                                  use_ref=True)
    err = _attn_err(got, want)
    log(f"parity flash_attention causal [S={sk}, H={h}/{hkv}, D={hd}] "
        f"{cfg.dtype}: max err {err:.3f} of the bf16 tolerance "
        f"{ATTN_TOL:.3e} * (1 + |ref|)")
    assert np.isfinite(err) and err <= 1.0, err


def shard_parity(jax, seed: int):
    """PR 3's contract across chips: shard_search_batch over every device
    equals search_batch on one device, root for root, bit for bit."""
    import numpy as np
    from repro.core.domains.pgame import PGameDomain
    from repro.search import (SearchConfig, SearchParams, search_batch,
                              shard_search_batch)
    # roots differ in an array-valued field (search_batch stacks those)
    doms = [PGameDomain(num_actions=4, game_depth=8, seed=seed,
                        threshold=0.3 + 0.05 * i) for i in range(8)]
    cfg = SearchConfig(method="pipeline", budget=256, lanes=8,
                       params=SearchParams(cp=0.7, max_depth=8))
    key = jax.random.key(seed)
    one = np.asarray(search_batch(doms, cfg, key, mesh=False).action_visits)
    many = np.asarray(shard_search_batch(doms, cfg, key).action_visits)
    log(f"shard parity over {jax.device_count()} devices, 8 roots: "
        f"equal={np.array_equal(one, many)}; root 0 {one[0].tolist()}")
    assert np.array_equal(one, many), "sharded != single-device search"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = device_facts(jax)
    if dev["count"] < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found "
                 f"{dev['count']} device(s)")
    from repro.launch.compile_cache import enable_compile_cache
    log(f"device {dev}; jax {jax.__version__}; compile cache "
        f"{enable_compile_cache()}")
    from repro.serving.mcts_decode import MCTSDecodeConfig
    cfg, params = model(args.seed)

    if args.chips == 4:
        shard_parity(jax, args.seed)
        serve(jax, cfg, params, MCTSDecodeConfig(), args.seed,
              f"cold, mesh over {dev['count']} chips")
    else:
        serve(jax, cfg, params, MCTSDecodeConfig(), args.seed, "cold")
        serve(jax, cfg, params,
              MCTSDecodeConfig(kv_splice=True, tree_reuse=True), args.seed,
              "stateful (kv_splice + tree_reuse)")
        search_parity(jax, args.seed)
        attention_parity(jax, cfg, args.seed)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
