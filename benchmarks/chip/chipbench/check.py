"""The comparison that decides ``correct``: served tokens against the plain
float32 reference.

Each token the engine commits is one of the root's top-A continuations under
the served model (A = the search's ``num_actions``); the search picks which.
So for each sampled request the reference runs once over its prompt and its
served tokens, and at every served position reads by how much the served
token's logit lies below the reference's A-th best logit there (0 where it is
inside the reference's top-A).  The number compared, ``top_a_gap``, is the
widest such gap over the sample.

The precision control is put in the program's place: at the same positions
of the same prompts and served tokens, it commits the entry of its own
lower-precision top-A at an index drawn from the seed (the search may commit
any of the A), judged by the same number and limit.

What the number cannot see: the committed token is the root prefill's own
top-A entry at the search's pick, so the playouts (their cached decode
through ``decode_attention``, the per-node KV copies) and the pick itself
only choose among tokens that all pass.
"""
from __future__ import annotations

import functools
import json
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

Served = Tuple[np.ndarray, List[int]]      # (prompt, served tokens)


@functools.lru_cache(maxsize=None)
def _gap_fn(ref, conf_json: str, A: int, S: int, control: Optional[str]):
    """jit: (weights, tokens [S], plen, n, picks [S]) -> (widest gap, tokens
    outside the reference's top-A, count of tokens at each reference rank
    0..A-1 and A for outside).  Without ``control`` the tokens judged are
    the served ones; with it, entry ``picks`` of the control's own top-A."""
    import jax
    import jax.numpy as jnp
    conf = json.loads(conf_json)

    def f(w, toks, plen, n, picks):
        ref_lg = ref.logits(conf, w, toks)                        # [S, V]
        thr = jax.lax.top_k(ref_lg, A)[0][:, A - 1]
        nxt = jnp.roll(toks, -1)
        if control is not None:
            top = jax.lax.top_k(ref.logits(conf, w, toks, quant=control),
                                A)[1]
            nxt = jnp.take_along_axis(top, picks[:, None], 1)[:, 0]
        judged = jnp.take_along_axis(ref_lg, nxt[:, None], 1)[:, 0]
        p = jnp.arange(S)
        at = (p >= plen - 1) & (p < plen - 1 + n)
        gap = jnp.where(at, jnp.maximum(thr - judged, 0.0), 0.0)
        rank = jnp.minimum(jnp.sum(ref_lg > judged[:, None], 1), A)
        ranks = jnp.sum(at[:, None] & (rank[:, None] == jnp.arange(A + 1)),
                        0)
        return jnp.max(gap), ranks[A], ranks

    return jax.jit(f)


def sample(done: Sequence[Served], seed: int, k: int) -> List[Served]:
    """The longest request and up to ``k - 1`` others drawn from ``seed``."""
    if not done:
        return []
    order = sorted(range(len(done)),
                   key=lambda i: -(len(done[i][0]) + len(done[i][1])))
    rest = order[1:]
    rng = np.random.default_rng(int(seed) + 1)
    picked = rng.permutation(rest)[: max(k - 1, 0)].tolist() if rest else []
    return [done[i] for i in [order[0]] + sorted(picked)]


def compare(ref, conf: Dict, seed: int, served: Sequence[Served], A: int,
            S: int, control: Optional[str] = None) -> Dict:
    """Regenerate the weights from ``seed`` and read every gap of
    ``served``, each padded to ``S`` positions; with ``control`` (a
    precision of ``ref.logits``), the gaps of the control put in the
    program's place."""
    import jax.numpy as jnp
    w = ref.make_weights(conf, seed, conf["serve_dtype"])
    key = json.dumps(conf, sort_keys=True)
    fn = _gap_fn(ref, key, A, S, control)
    rng = np.random.default_rng(int(seed) + 2)
    out = {"top_a_gap": 0.0, "gaps": [], "tokens_checked": 0,
           "outside_top_a": 0, "ranks": [0] * (A + 1)}
    for prompt, toks in served:
        seq = np.zeros((S,), np.int32)
        seq[: len(prompt)] = prompt
        seq[len(prompt): len(prompt) + len(toks)] = toks
        picks = rng.integers(0, A, size=S).astype(np.int32)
        g, outside, ranks = fn(w, jnp.asarray(seq), jnp.int32(len(prompt)),
                               jnp.int32(len(toks)), jnp.asarray(picks))
        out["gaps"].append(float(g))
        out["outside_top_a"] += int(outside)
        out["tokens_checked"] += len(toks)
        out["ranks"] = [a + int(b) for a, b in zip(out["ranks"], ranks)]
    out["top_a_gap"] = max(out["gaps"], default=0.0)
    return out
