"""One module per ``model_type``: everything in the benchmark that depends
on a model's architecture. ``harness.arch`` finds ``archs/<model_type>.py``
by the ``model_type`` of a configuration file; nothing else in ``bench/``
names an architecture. A module whose name starts with ``_`` is shared code,
not an architecture.

Each architecture module exports (``harness.ARCH_CONTRACT``):

* ``spec(config) -> Spec``: the file's sizes as a frozen, hashable
  dataclass with at least ``n_layers`` and ``vocab``; raises on a key whose
  value the module does not implement;
* ``program_config(config, spec, *, page) -> ArchConfig``: the program's
  configuration for the file, checked key by key against it;
* ``canonical(spec, seed)``: the weights in a plain layout, and
  ``program_params(spec, seed)``: the same draws in the program's parameter
  tree, both through ``weights.py``'s ``seed_key_data`` and ``draw``;
* ``logits_at(spec, w, tokens, rows, precision)``: the plain reference's
  next-token logits of ``tokens`` at ``rows``, ``precision`` ``"f32"`` or
  ``"fp8"`` (the control), built from ``reference.py``'s pieces;
* ``matmul_weights(spec) -> (layers, head)``: the matmul weights one token
  passes through, the *active* ones over every layer and the LM head;
* ``attn_flops_per_pair(spec)``: what ``q.k`` and ``p.v`` cost for one
  (query, key) pair, summed over the layers.
"""
