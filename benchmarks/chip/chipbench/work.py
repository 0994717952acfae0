"""The model operations a cell's work needs, counted from the configuration
and the traffic alone, never from the program, so that the count is the
same whatever implements it.

* Each committed token costs ``budget * (1 + rollout_len)`` single-token
  model steps: one expansion and the rollout of each playout.  Each such
  step is charged at the position of the root it searches from (the prompt
  plus the tokens committed before it), which undercounts the few positions
  the tree and the rollout add.
* Each request costs one prefill of its prompt; the LM head runs at its
  last position only.
* A model token costs 2 x the matrix-product weights (the tied LM head
  included) plus its attention over the positions before it.
* The cold searcher's prefill of the whole prefix on every token is
  recomputation and does not count.
"""
from __future__ import annotations

from typing import Dict


def token_flops(ref, conf: Dict, position: int) -> int:
    return 2 * ref.matmul_params(conf) + ref.attention_flops(conf, position)


def prefill_flops(ref, conf: Dict, plen: int) -> int:
    body = 2 * (ref.matmul_params(conf) - ref.head_params(conf)) * plen
    head = 2 * ref.head_params(conf)
    attn = sum(ref.attention_flops(conf, i) for i in range(1, plen + 1))
    return body + head + attn


def committed_token_flops(ref, conf: Dict, search: Dict, position: int) -> int:
    steps = search["budget"] * (1 + search["rollout_len"])
    return steps * token_flops(ref, conf, position)
