"""The architecture modules (``archs/<model_type>.py``): each exports the
contract; the harness names the missing module for an unknown
``model_type``; and the dense architectures give, to the bit, the weights,
reference logits and FLOP counts recorded in
``data/arch_fingerprints.json`` before the modules existed."""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from conftest import ROOT
from reference import Q_BLOCK
from tiny import TINY_CONFIG, TINY_STARCODER2_CONFIG, TINY_TRAFFIC

with open(os.path.join(os.path.dirname(__file__), "data",
                       "arch_fingerprints.json")) as f:
    RECORDED = json.load(f)
CONFIGS = {"qwen2": TINY_CONFIG, "starcoder2": TINY_STARCODER2_CONFIG}
ARCHS = sorted(n[:-3] for n in os.listdir(os.path.join(harness.BENCH_DIR,
                                                        "archs"))
               if n.endswith(".py") and not n.startswith("_"))
BENCH = harness.load_benchmark(ROOT)


def _sha(x) -> str:
    a = np.asarray(x)
    h = hashlib.sha256(f"{a.dtype}{a.shape}".encode())
    h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def _fingerprint(arch, spec, seed):
    w = arch.canonical(spec, seed)
    leaves, _ = jax.tree_util.tree_flatten_with_path(
        arch.program_params(spec, seed))
    toks = jnp.asarray((7 * np.arange(Q_BLOCK) + 3) % spec.vocab, jnp.int32)
    rows = jnp.asarray(RECORDED["rows"], jnp.int32)
    model = harness.cost("model")
    return {
        "canonical": {k: _sha(v) for k, v in sorted(w.items())},
        "program_params": {jax.tree_util.keystr(k): _sha(v)
                           for k, v in leaves},
        "logits": {p: _sha(arch.logits_at(spec, w, toks, rows, p))
                   for p in ("f32", "fp8")},
        "decode_step": model.decode_step(arch, spec, RECORDED["contexts"]),
        "prefill": {str(n): model.prefill(arch, spec, n)
                    for n in RECORDED["prefills"]},
    }


@pytest.mark.parametrize("seed", RECORDED["seeds"])
@pytest.mark.parametrize("model_type", sorted(CONFIGS))
def test_dense_fingerprints_match_the_record(model_type, seed):
    conf = CONFIGS[model_type]
    arch = harness.arch(conf["model_type"])
    got = _fingerprint(arch, arch.spec(conf), seed)
    assert got == RECORDED["fingerprints"][f"{model_type}/{seed}"]


def test_unknown_model_type_names_the_missing_module():
    conf = dict(TINY_CONFIG, model_type="no_such_arch")
    with pytest.raises(ValueError, match=r"bench/archs/no_such_arch\.py"):
        harness.Cell("tiny.cell", 1, conf, TINY_TRAFFIC, [], [])
    with pytest.raises(ValueError, match=r"bench/archs/_dense\.py"):
        harness.arch("_dense")


@pytest.mark.parametrize("name", ARCHS)
def test_arch_module_exports_the_contract(name):
    mod = harness.arch(name)
    missing = [k for k in harness.ARCH_CONTRACT
               if not callable(getattr(mod, k, None))]
    assert not missing, f"archs/{name}.py lacks {missing}"


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_reads_into_a_hashable_spec(conf):
    with open(os.path.join(ROOT, conf["file"])) as f:
        c = json.load(f)
    arch = harness.arch(c["model_type"])
    spec = arch.spec(c)
    with pytest.raises(dataclasses.FrozenInstanceError):
        spec.n_layers = 0
    assert hash(spec) == hash(arch.spec(c)) and spec == arch.spec(c)
    assert (spec.n_layers, spec.vocab) == (c["num_hidden_layers"],
                                          c["vocab_size"])
    layers, head = arch.matmul_weights(spec)
    assert layers > 0 and head > 0 and arch.attn_flops_per_pair(spec) > 0
