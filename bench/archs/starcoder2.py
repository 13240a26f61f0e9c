"""StarCoder2 (``model_type: starcoder2``): the dense decoder with
LayerNorm, a tanh-GELU MLP with biases and, with ``use_bias``, biases on
q, k and v."""

from archs import _dense
from archs._dense import (Spec, attn_flops_per_pair, canonical,  # noqa: F401
                          logits_at, matmul_weights, program_config,
                          program_params)


def spec(c) -> Spec:
    _dense.expect(c, hidden_act="gelu_pytorch_tanh", norm_type="layer_norm")
    return _dense.make_spec(c, norm="layernorm", norm_eps=c["norm_epsilon"],
                            act="gelu", qkv_bias=bool(c["use_bias"]))
