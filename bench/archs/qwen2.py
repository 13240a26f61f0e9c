"""Qwen2 (``model_type: qwen2``): the dense decoder with RMSNorm, SwiGLU
and biases on q, k and v."""

from archs import _dense
from archs._dense import (Spec, attn_flops_per_pair, canonical,  # noqa: F401
                          logits_at, matmul_weights, program_config,
                          program_params)


def spec(c) -> Spec:
    _dense.expect(c, hidden_act="silu")
    return _dense.make_spec(c, norm="rmsnorm", norm_eps=c["rms_norm_eps"],
                            act="swiglu", qkv_bias=True)
