"""A cell small enough for the CPU, driven through the harness's own run."""
import pathlib

from chipbench import spec

CONFIG = {"name": "tiny", "reference": "dense", "hidden_size": 64,
          "intermediate_size": 128, "num_hidden_layers": 2,
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "vocab_size": 256, "hidden_act": "silu", "attention_bias": True,
          "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
          "tie_word_embeddings": True, "initializer_range": 0.1,
          "serve_dtype": "float32"}
TRAFFIC = {"loop": "closed", "clients_per_slot": 2, "slots_per_chip": 2,
           "max_seq": 32,
           "prompt_len": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                          "min": 4, "max": 16},
           "output_len": {"dist": "lognormal", "median": 3, "sigma": 0.3,
                          "min": 2, "max": 4},
           "sizes_per_cycle": 8,
           "search": {"method": "pipeline", "budget": 4, "lanes": 2,
                      "search_depth": 2, "rollout_len": 1, "num_actions": 4,
                      "temperature": 1.0, "cp": 1.0},
           "warmup_steps": 2, "trace_steps": 3, "check_requests": 8}
# the tiny program serves in float32, so sound runs read ~1e-6
LIMIT = 1e-3


def workload(limit: float = LIMIT) -> spec.Workload:
    bench = spec.load_benchmark()
    return spec.Workload(
        name="tiny.chat", chips=1, config=CONFIG, traffic=TRAFFIC,
        reference=spec.reference("dense"),
        limits={"top_a_gap": {"limit": limit}},
        end_to_end=spec.metrics_for(bench["end_to_end"], "tiny.chat"),
        per_layer=spec.metrics_for(bench["per_layer"], "tiny.chat"))


def run(tmp: pathlib.Path, seed: int = 3, **kw):
    from chipbench import cell
    return cell.run(workload(), seed, 2.0, False, 0.0, tmp / "cache",
                    require_chip=False, **kw)
