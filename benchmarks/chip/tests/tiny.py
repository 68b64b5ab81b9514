"""A tiny configuration and traffic for running the loops on the CPU."""
import copy
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent

TINY_MODEL = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 256,
    "max_position_embeddings": 128, "rms_norm_eps": 1e-05,
    "rope_theta": 10000.0, "hidden_act": "silu",
    "tie_word_embeddings": False,
}


def config(layout=None) -> dict:
    cfg = json.loads((HERE.parent / "configs" / "smollm-360m.json")
                     .read_text())
    cfg = copy.deepcopy(cfg)
    cfg["name"] = "tiny"
    cfg["model"] = dict(TINY_MODEL)
    # CPU: the jnp attention path (auto resolves to it off-TPU)
    if layout:
        cfg["layout"] = layout
    return cfg


TRAIN = {"loop": "train", "seq_len": 32, "global_batch": 4, "ref_rows": 2,
         "trace_steps": 2,
         "limits": {"loss_gap": 1e-3, "grad_gap": 3e-3, "update_gap": 1e-3}}

SERVE = {"loop": "serve", "rate_per_s": 20.0, "shape_seed": 7,
         "prompt_len": {"median": 12, "sigma": 0.6, "min": 4},
         "output_len": {"min": 3, "max": 8},
         "engine": {"n_slots": 4, "block_size": 8, "num_blocks": 64,
                    "max_seq_len": 64},
         "sample_requests": 3, "drain_s": 30, "trace_seconds": 0.5,
         "limits": {"logit_gap": 1e-3}}
