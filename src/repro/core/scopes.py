"""Device scope names of the search's stages (``jax.named_scope``).

Every op of a search program is traced under one of six names, which XLA
keeps in each instruction's ``op_name`` metadata and a profile shows beside
the op's device time:

* ``search.root``       — the root's state: the prompt prefill, the
  root's own top-A, the spliced root row's advance and its admission;
* ``search.tree``       — tree bookkeeping: select, the structural expand,
  backup, reroot, and the search-wave kernel launches;
* ``search.expand``     — the model step of each expanded child;
* ``search.playout``    — rollouts and priors;
* ``search.node_state`` — per-node state moved in and out of the arena:
  parent-state gathers, child-state scatters, per-lane selects;
* ``search.topk``       — every top-A over the vocabulary.

Scopes nest; an op belongs to the innermost of the six in its ``op_name``
(``stage_of``), so the six split a program's ops into disjoint parts.
"""
from __future__ import annotations

import re
from typing import Optional

ROOT = "search.root"
TREE = "search.tree"
EXPAND = "search.expand"
PLAYOUT = "search.playout"
NODE_STATE = "search.node_state"
TOPK = "search.topk"
STAGES = (ROOT, TREE, EXPAND, PLAYOUT, NODE_STATE, TOPK)

# a stage is one whole ``op_name`` component, or the argument of a
# transform wrapping one (``vmap(search.topk)``)
_STAGE = re.compile(r"(?:^|[/(])(%s)(?=[/)]|$)"
                    % "|".join(re.escape(s) for s in STAGES))


def stage_of(op_name: str) -> Optional[str]:
    """The innermost stage scope in an ``op_name``, or None."""
    found = _STAGE.findall(op_name)
    return found[-1] if found else None
