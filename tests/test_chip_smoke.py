"""``chip_smoke.py`` off the chip: it refuses a machine without a TPU, and
its phases pass on the CPU at a tiny size (the kernels in interpret mode),
so the script cannot drift from the code it drives."""
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.configs import get_smoke_config
from repro.models.base import get_family
from repro.serving.mcts_decode import MCTSDecodeConfig

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
import chip_smoke  # noqa: E402

TINY = MCTSDecodeConfig(budget=4, lanes=2, search_depth=2, rollout_len=1)


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_custom_call_counts():
    hlo = "\n".join([
        '%a = custom-call(), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(step)/search_wave_bes/pallas_call"}',
        '%b = custom-call(), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(step)/decode_attention/pallas_call"}',
        '%c = custom-call(), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(step)/decode_attention/pallas_call"}',
        '%d = fusion(%a), metadata={op_name="jit(step)/search_wave"}',
    ])
    assert chip_smoke.custom_calls(hlo) == {
        "search_wave": 1, "decode_attention": 2, "flash_attention": 0,
        "uct_select": 0, "total": 3}


@pytest.mark.parametrize("stateful", (False, True))
def test_serve_phase_tiny(stateful):
    cfg = get_smoke_config("smollm-135m")
    params = get_family(cfg).init(cfg, jax.random.key(0))
    dcfg = TINY if not stateful else MCTSDecodeConfig(
        budget=4, lanes=2, search_depth=2, rollout_len=1, kv_splice=True,
        tree_reuse=True)
    toks = chip_smoke.serve(jax, cfg, params, dcfg, 0, "tiny",
                            kernels=False)
    assert len(toks) == chip_smoke.N_REQUESTS * chip_smoke.MAX_NEW


def test_attention_parity_interpret():
    chip_smoke.attention_parity(jax, get_smoke_config("smollm-135m"), 0,
                                interpret=True)
