"""The operation counts behind ``step_mfu``, against the program's own
parameter count."""
import json

import jax
import pytest

from chipbench import spec, work
from repro.models.base import ModelConfig, abstract_params, count_params


@pytest.mark.parametrize("name", ["smollm-135m", "qwen2-0.5b"])
def test_matmul_params_match_the_program(name):
    conf = json.load(open(spec.HERE / "configs" / f"{name}.json"))
    ref = spec.reference(conf["reference"])
    cfg = ModelConfig(**ref.program_config(conf))
    total = count_params(abstract_params(cfg))
    n = ref.dims(conf)
    norms = (2 * n["L"] + 1) * n["d"]
    biases = n["L"] * (n["h"] + 2 * n["hkv"]) * n["hd"] if n["bias"] else 0
    # the tied embedding is counted once, as the LM head
    assert ref.matmul_params(conf) == total - norms - biases
    shapes = jax.eval_shape(
        lambda: ref.make_weights(conf, 0, conf["serve_dtype"]))
    assert count_params(shapes) == total


def test_counts():
    conf = json.load(open(spec.HERE / "configs" / "smollm-135m.json"))
    ref = spec.reference("dense")
    m = ref.matmul_params(conf)
    assert m == 134_479_872
    assert work.token_flops(ref, conf, 0) == 2 * m
    # attention over 100 positions: 4 * layers * heads * head_dim * 100
    assert work.token_flops(ref, conf, 100) - 2 * m == 4 * 30 * 9 * 64 * 100
    search = {"budget": 32, "rollout_len": 4}
    assert (work.committed_token_flops(ref, conf, search, 100)
            == 160 * work.token_flops(ref, conf, 100))
    one = work.prefill_flops(ref, conf, 1)
    assert one == 2 * m + 4 * 30 * 9 * 64
