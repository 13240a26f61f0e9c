"""A cell small enough for the CPU: Qwen1.5's layer math at toy widths
(the Pallas kernels run in the interpreter there), and a StarCoder2-shaped
configuration at the same widths."""

import harness

# widths as small as the kernels' 128-lane words allow (two heads of 64
# per word); the limit sits between the sound runs' widest gaps on the CPU
# (0.0071-0.0142 over 3 seeds) and the fp8 control's (0.123-0.209)
TINY_CONFIG = {
    "name": "tiny", "repro_arch": "qwen1_5_0p5b", "model_type": "qwen2",
    "hidden_act": "silu", "hidden_size": 256, "intermediate_size": 512,
    "num_attention_heads": 4, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
    "vocab_size": 512, "limit_logit_gap": 0.05,
}
TINY_TRAFFIC = {
    "name": "tiny", "loop": "closed", "clients": 3, "page": 16,
    "prompt_len": {"dist": "lognormal", "median": 20, "sigma": 0.5,
                   "min": 8, "max": 40},
    "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.4,
                   "min": 4, "max": 12},
    "requests_per_window_second": 1.0, "check_tokens": 30,
}
SECONDS = 6

# LayerNorm, tanh-GELU with biases, GQA 4:1; read by its architecture
# module, never served
TINY_STARCODER2_CONFIG = {
    "name": "tiny_starcoder2", "repro_arch": "starcoder2_15b",
    "model_type": "starcoder2", "hidden_act": "gelu_pytorch_tanh",
    "norm_type": "layer_norm", "norm_epsilon": 1e-5, "use_bias": True,
    "hidden_size": 256, "intermediate_size": 512,
    "num_attention_heads": 4, "num_key_value_heads": 1,
    "num_hidden_layers": 2, "rope_theta": 1e5, "vocab_size": 512,
    "tie_word_embeddings": False,
}


def tiny_cell(end_to_end=(), per_layer=()):
    return harness.Cell(
        "tiny.cell", 1, TINY_CONFIG, TINY_TRAFFIC,
        [{"name": n, "unit": "1"} for n in end_to_end],
        [{"name": n, "unit": "1"} for n in per_layer])
