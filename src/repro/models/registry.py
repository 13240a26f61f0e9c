"""Model registry: one Model class per family, a single interface for the
trainer, server, dry-run, and tests.

Entry points per shape kind:
  train   -> loss(params, batch)                 batch: tokens/labels (+frontend)
  prefill -> prefill(params, batch)              -> (last-token logits, cache)
  decode  -> decode_step(params, batch, cache)   -> (logits, new cache)

``input_specs(shape)`` returns ShapeDtypeStruct stand-ins for every input of
the entry point (weak-type-correct, shardable, no allocation) — the dry-run
contract. ``cache_spec(shape)`` ditto for KV/state caches.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, ShapeConfig, get_config
from repro.models import encdec, hybrid, layers as L, mla, moe, rwkv6, transformer
from repro.runtime.sharding import constrain


def _token_specs(batch: int, seq: int) -> Dict[str, Any]:
    return {
        "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
    }


_TOKEN_AXES = {"tokens": ("batch", "seq"), "labels": ("batch", "seq")}


class BaseLM:
    """Decoder-only LM; mixer/ffn hooks cover dense, MoE, and MLA."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg
        k = cfg.first_k_dense
        self.stack = transformer.DecoderStack(
            cfg.replace(n_layers=cfg.n_layers - k) if k else cfg,
            mixer_specs=self._mixer_specs(),
            mixer_apply=self._mixer_apply(),
            mixer_cache_spec=self._mixer_cache_spec(),
            ffn_specs=self._ffn_specs(),
            ffn_apply=self._ffn_apply(),
        )
        # leading dense-FFN layers (deepseek's first_k_dense_replace), a
        # scanned stack of their own in front of the main one
        self.dense_stack = transformer.DecoderStack(
            cfg.replace(n_layers=k),
            mixer_specs=self._mixer_specs(),
            mixer_apply=self._mixer_apply(),
            mixer_cache_spec=self._mixer_cache_spec()) if k else None

    # hooks ------------------------------------------------------------------
    def _mixer_specs(self):
        return transformer.attn_specs

    def _mixer_apply(self):
        return transformer.attn_apply

    def _mixer_cache_spec(self):
        return transformer.attn_cache_spec

    def _ffn_specs(self):
        return transformer.ffn_specs

    def _ffn_apply(self):
        return transformer.ffn_apply

    # params -------------------------------------------------------------------
    def param_specs(self):
        cfg = self.cfg
        s = {
            "embed": L.embed_specs(cfg.padded_vocab, cfg.d_model),
            "stack": self.stack.specs(),
            "final_norm": L.norm_specs(cfg.norm, cfg.d_model),
        }
        if self.dense_stack is not None:
            s["dense_stack"] = self.dense_stack.specs()
        if not cfg.tie_embeddings:
            s["unembed"] = L.ParamSpec((cfg.padded_vocab, cfg.d_model),
                                       ("vocab", "embed"))
        return s

    def init(self, key):
        return L.init_params(self.param_specs(), key)

    def abstract_params(self):
        return L.abstract_params(self.param_specs())

    def param_axes(self):
        return L.param_axes(self.param_specs())

    def param_count(self) -> int:
        return L.param_count(self.param_specs())

    def active_param_count(self) -> int:
        cfg = self.cfg
        n = self.param_count()
        if cfg.n_experts and cfg.top_k:
            per_expert = cfg.d_model * 3 * cfg.moe_d_ff
            inactive = (cfg.n_layers - cfg.first_k_dense) * max(
                cfg.experts_held - cfg.top_k, 0) * per_expert
            n -= inactive
        return n

    def _stacks(self, params, x, *, positions, caches=None, lengths=None,
                want_cache: bool = False):
        """The leading dense layers (if any), then the main stack. Caches
        carry one leading layer axis over both (the dense layers first);
        a decode step's per-row expert assignments (``expert_counts``) come
        back summed over the layers, [B, n_experts]."""
        k = self.cfg.first_k_dense
        if isinstance(caches, dict):    # a step's output fed back in
            caches = {n: c for n, c in caches.items() if n != "expert_counts"}
        if k and caches is not None and "kv_pool" in caches:
            return self._paged_stacks(params, x, positions=positions,
                                      caches=caches, lengths=lengths)
        parts = []
        aux = jnp.zeros((), jnp.float32)
        for stack, name, sl in ((self.dense_stack, "dense_stack",
                                 slice(0, k)),
                                (self.stack, "stack", slice(k, None))):
            if stack is None:
                continue
            c = (None if caches is None
                 else jax.tree.map(lambda a: a[sl], caches))
            x, nc, a = stack(params[name], x, positions=positions, caches=c,
                             lengths=lengths, want_cache=want_cache)
            parts.append(nc)
            aux = aux + a
        assign = [p.pop("expert_counts") for p in parts
                  if isinstance(p, dict) and "expert_counts" in p]
        new = parts[0] if len(parts) == 1 else (
            None if parts[-1] is None else jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0), *parts))
        if assign:
            new["expert_counts"] = sum(jnp.sum(a, axis=0) for a in assign)
        return x, new, aux

    def _paged_stacks(self, params, x, *, positions, caches, lengths):
        """A decode step through one paged pool over both stacks, each
        layer updating its own pool layer in place."""
        pool, bt = caches["kv_pool"], caches["block_tables"]
        aux = jnp.zeros((), jnp.float32)
        assign = []
        for stack, name, first in ((self.dense_stack, "dense_stack", 0),
                                   (self.stack, "stack",
                                    self.cfg.first_k_dense)):
            x, pool, a, au = stack.decode_pool(
                params[name], x, positions=positions, pool=pool,
                block_tables=bt, lengths=lengths, first=first)
            aux = aux + au
            if a is not None:
                assign.append(jnp.sum(a, axis=0))
        new = {"kv_pool": pool, "block_tables": bt}
        if assign:
            new["expert_counts"] = sum(assign)
        return x, new, aux

    # forward ------------------------------------------------------------------
    def _extra_embeds(self, params, batch) -> Optional[jnp.ndarray]:
        return None

    def _trunk(self, params, batch, *, want_cache: bool):
        cfg = self.cfg
        x = L.embed_lookup(params["embed"], batch["tokens"], cfg.cdtype)
        extra = self._extra_embeds(params, batch)
        n_extra = 0
        if extra is not None:
            x = jnp.concatenate([extra.astype(cfg.cdtype), x], axis=1)
            n_extra = extra.shape[1]
        positions = jnp.arange(x.shape[1])
        x, caches, aux = self._stacks(params, x, positions=positions,
                                      want_cache=want_cache)
        x = L.norm_apply(cfg.norm, x, params["final_norm"])
        return x, caches, aux, n_extra

    def _unembed(self, params):
        return params["embed"] if self.cfg.tie_embeddings else params["unembed"]

    def loss(self, params, batch):
        cfg = self.cfg
        x, _, aux, n_extra = self._trunk(params, batch, want_cache=False)
        if n_extra:
            x = x[:, n_extra:]
        if cfg.loss_chunk > 1:
            loss = L.chunked_unembed_loss(x, self._unembed(params),
                                          batch["labels"], cfg.loss_chunk)
        else:
            logits = L.unembed_logits(x, self._unembed(params))
            loss = L.cross_entropy(logits, batch["labels"])
        loss = loss + 0.01 * aux
        return loss, {"loss": loss, "aux": aux}

    def prefill(self, params, batch):
        x, caches, _, _ = self._trunk(params, batch, want_cache=True)
        logits = L.unembed_logits(x[:, -1:], self._unembed(params))[:, 0]
        return logits, caches

    def decode_step(self, params, batch, caches):
        cfg = self.cfg
        tok = batch["token"]
        lengths = batch["lengths"].astype(jnp.int32)
        x = L.embed_lookup(params["embed"], tok[:, None], cfg.cdtype)
        positions = lengths[:, None]
        x, new_caches, _ = self._stacks(params, x, positions=positions,
                                        caches=caches, lengths=lengths)
        x = L.norm_apply(cfg.norm, x, params["final_norm"])
        logits = L.unembed_logits(x, self._unembed(params))[:, 0]
        return logits, new_caches

    # specs ------------------------------------------------------------------
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        b, s = shape.global_batch, shape.seq_len
        if shape.kind == "train":
            return _token_specs(b, s)
        if shape.kind == "prefill":
            return {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}
        return {"token": jax.ShapeDtypeStruct((b,), jnp.int32),
                "lengths": jax.ShapeDtypeStruct((b,), jnp.int32)}

    def input_axes(self, shape: ShapeConfig):
        if shape.kind == "train":
            return dict(_TOKEN_AXES)
        if shape.kind == "prefill":
            return {"tokens": ("batch", "seq")}
        return {"token": ("batch",), "lengths": ("batch",)}

    def cache_spec(self, shape: ShapeConfig):
        return transformer.DecoderStack(
            self.cfg, mixer_cache_spec=self._mixer_cache_spec()).cache_spec(
                shape.global_batch, shape.seq_len)


class DenseLM(BaseLM):
    pass


class MoELM(BaseLM):
    def _ffn_specs(self):
        return moe.moe_ffn_specs

    def _ffn_apply(self):
        return moe.moe_ffn_apply


class MLAMoELM(MoELM):
    """deepseek-v2: MLA mixer + MoE FFN."""

    def _mixer_specs(self):
        return mla.mla_specs

    def _mixer_apply(self):
        return mla.mla_apply

    def _mixer_cache_spec(self):
        return mla.mla_cache_spec


class VLM(DenseLM):
    """internvl2: stubbed ViT patch embeddings prepended to the LM."""

    def param_specs(self):
        s = super().param_specs()
        d = self.cfg.d_model
        s["vision_proj"] = L.ParamSpec((d, d), ("embed", None))
        return s

    def _extra_embeds(self, params, batch):
        if "image_embeds" not in batch:
            return None
        x = batch["image_embeds"].astype(self.cfg.cdtype)
        return x @ params["vision_proj"].astype(x.dtype)

    def input_specs(self, shape: ShapeConfig):
        s = super().input_specs(shape)
        cfg = self.cfg
        if shape.kind in ("train", "prefill"):
            s["image_embeds"] = jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.n_patches, cfg.d_model), cfg.cdtype)
        return s

    def input_axes(self, shape: ShapeConfig):
        a = super().input_axes(shape)
        if shape.kind in ("train", "prefill"):
            a["image_embeds"] = ("batch", "patches", "embed")
        return a

    def cache_spec(self, shape: ShapeConfig):
        # cache covers patches + tokens
        return self.stack.cache_spec(shape.global_batch,
                                     shape.seq_len + self.cfg.n_patches)


class ZambaLM(BaseLM):
    """zamba2 hybrid (Mamba2 + shared attention)."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    def param_specs(self):
        cfg = self.cfg
        return {
            "embed": L.embed_specs(cfg.padded_vocab, cfg.d_model),
            "stack": hybrid.specs(cfg),
            "final_norm": L.norm_specs(cfg.norm, cfg.d_model),
            "unembed": L.ParamSpec((cfg.padded_vocab, cfg.d_model),
                                   ("vocab", "embed")),
        }

    def _trunk(self, params, batch, *, want_cache, caches=None, lengths=None):
        cfg = self.cfg
        if "token" in batch:
            x = L.embed_lookup(params["embed"], batch["token"][:, None],
                               cfg.cdtype)
            positions = lengths[:, None]
        else:
            x = L.embed_lookup(params["embed"], batch["tokens"], cfg.cdtype)
            positions = jnp.arange(x.shape[1])
        x, new_caches, aux = hybrid.forward(
            cfg, params["stack"], x, positions=positions, caches=caches,
            lengths=lengths, want_cache=want_cache)
        x = L.norm_apply(cfg.norm, x, params["final_norm"])
        return x, new_caches, aux

    def loss(self, params, batch):
        x, _, aux = self._trunk(params, batch, want_cache=False)
        if self.cfg.loss_chunk > 1:
            loss = L.chunked_unembed_loss(x, params["unembed"],
                                          batch["labels"],
                                          self.cfg.loss_chunk)
        else:
            logits = L.unembed_logits(x, params["unembed"])
            loss = L.cross_entropy(logits, batch["labels"])
        return loss, {"loss": loss, "aux": aux}

    def prefill(self, params, batch):
        x, caches, _ = self._trunk(params, batch, want_cache=True)
        logits = L.unembed_logits(x[:, -1:], params["unembed"])[:, 0]
        return logits, caches

    def decode_step(self, params, batch, caches):
        lengths = batch["lengths"].astype(jnp.int32)
        x, new_caches, _ = self._trunk(params, batch, want_cache=True,
                                       caches=caches, lengths=lengths)
        logits = L.unembed_logits(x, params["unembed"])[:, 0]
        return logits, new_caches

    def cache_spec(self, shape: ShapeConfig):
        return hybrid.cache_spec(self.cfg, shape.global_batch, shape.seq_len)


class RWKVLM(BaseLM):
    """rwkv6: token-shift time/channel mixing, attention-free."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    def param_specs(self):
        cfg = self.cfg
        one = {
            "ln1": L.norm_specs("layernorm", cfg.d_model),
            "tm": rwkv6.time_mix_specs(cfg),
            "ln2": L.norm_specs("layernorm", cfg.d_model),
            "cm": rwkv6.channel_mix_specs(cfg),
        }
        stacked = jax.tree.map(
            lambda s: L.ParamSpec((cfg.n_layers, *s.shape),
                                  ("layers", *s.axes), s.dtype, s.init,
                                  s.scale),
            one, is_leaf=L.is_spec)
        return {
            "embed": L.embed_specs(cfg.padded_vocab, cfg.d_model),
            "ln0": L.norm_specs("layernorm", cfg.d_model),
            "layers": stacked,
            "final_norm": L.norm_specs("layernorm", cfg.d_model),
            "unembed": L.ParamSpec((cfg.padded_vocab, cfg.d_model),
                                   ("vocab", "embed")),
        }

    def _layer(self, p, x, cache):
        cfg = self.cfg
        h = L.norm_apply("layernorm", x, p["ln1"])
        tm_out, tm_cache = rwkv6.time_mix_apply(cfg, p["tm"], h, cache=cache)
        x = x + tm_out
        h = L.norm_apply("layernorm", x, p["ln2"])
        cm_out, cm_cache = rwkv6.channel_mix_apply(cfg, p["cm"], h,
                                                   cache=cache)
        x = x + cm_out
        x = constrain(x, ("batch", "seq_sp", "embed"))
        return x, {**tm_cache, **cm_cache}

    def _trunk(self, params, x, caches, want_cache):
        cfg = self.cfg
        layer = self._layer
        if cfg.remat != "none":
            layer = jax.checkpoint(
                layer, policy=jax.checkpoint_policies.nothing_saveable)
        if not cfg.scan_layers:
            outs = []
            for i in range(cfg.n_layers):
                p = jax.tree.map(lambda a: a[i], params["layers"])
                c = (jax.tree.map(lambda a: a[i], caches)
                     if caches is not None else None)
                x, nc = layer(p, x, c)
                outs.append(nc if (want_cache or caches is not None) else None)
            new_caches = (jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
                          if outs[0] is not None else None)
        elif caches is None:
            def body(xx, p):
                xx, nc = layer(p, xx, None)
                return xx, (nc if want_cache else None)
            x, new_caches = jax.lax.scan(body, x, params["layers"])
        else:
            def body(xx, xs):
                p, c = xs
                xx, nc = layer(p, xx, c)
                return xx, nc
            x, new_caches = jax.lax.scan(body, x, (params["layers"], caches))
        return L.norm_apply("layernorm", x, params["final_norm"]), new_caches

    def loss(self, params, batch):
        cfg = self.cfg
        x = L.embed_lookup(params["embed"], batch["tokens"], cfg.cdtype)
        x = L.norm_apply("layernorm", x, params["ln0"])
        x, _ = self._trunk(params, x, None, want_cache=False)
        if cfg.loss_chunk > 1:
            loss = L.chunked_unembed_loss(x, params["unembed"],
                                          batch["labels"], cfg.loss_chunk)
        else:
            logits = L.unembed_logits(x, params["unembed"])
            loss = L.cross_entropy(logits, batch["labels"])
        return loss, {"loss": loss}

    def prefill(self, params, batch):
        cfg = self.cfg
        x = L.embed_lookup(params["embed"], batch["tokens"], cfg.cdtype)
        x = L.norm_apply("layernorm", x, params["ln0"])
        x, caches = self._trunk(params, x, None, want_cache=True)
        logits = L.unembed_logits(x[:, -1:], params["unembed"])[:, 0]
        return logits, caches

    def decode_step(self, params, batch, caches):
        cfg = self.cfg
        x = L.embed_lookup(params["embed"], batch["token"][:, None],
                           cfg.cdtype)
        x = L.norm_apply("layernorm", x, params["ln0"])
        x, new_caches = self._trunk(params, x, caches, want_cache=True)
        logits = L.unembed_logits(x, params["unembed"])[:, 0]
        return logits, new_caches

    def cache_spec(self, shape: ShapeConfig):
        cfg = self.cfg
        one, one_axes = rwkv6.rwkv_cache_spec(cfg, shape.global_batch)
        spec = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((cfg.n_layers, *s.shape), s.dtype),
            one)
        axes = jax.tree.map(lambda a: ("layers", *a), one_axes,
                            is_leaf=lambda x: isinstance(x, tuple))
        return spec, axes


class EncDecLM(BaseLM):
    """whisper-tiny: stubbed conv frontend + enc-dec transformer."""

    def __init__(self, cfg: ArchConfig):
        self.cfg = cfg

    def param_specs(self):
        cfg = self.cfg
        return {
            "embed": L.embed_specs(cfg.padded_vocab, cfg.d_model),
            "encdec": encdec.specs(cfg),
            "unembed": L.ParamSpec((cfg.padded_vocab, cfg.d_model),
                                   ("vocab", "embed")),
        }

    def loss(self, params, batch):
        cfg = self.cfg
        enc_out = encdec.encode(cfg, params["encdec"], batch["frames"])
        x = L.embed_lookup(params["embed"], batch["tokens"], cfg.cdtype)
        pos = params["encdec"]["dec_pos"][:x.shape[1]].astype(x.dtype)
        x = x + pos[None]
        positions = jnp.arange(x.shape[1])
        x, _ = encdec.decode_stack(cfg, params["encdec"], x, enc_out,
                                   positions=positions)
        logits = L.unembed_logits(x, params["unembed"])
        loss = L.cross_entropy(logits, batch["labels"])
        return loss, {"loss": loss}

    def prefill(self, params, batch):
        cfg = self.cfg
        enc_out = encdec.encode(cfg, params["encdec"], batch["frames"])
        x = L.embed_lookup(params["embed"], batch["tokens"], cfg.cdtype)
        x = x + params["encdec"]["dec_pos"][:x.shape[1]].astype(x.dtype)[None]
        positions = jnp.arange(x.shape[1])
        x, caches = encdec.decode_stack(cfg, params["encdec"], x, enc_out,
                                        positions=positions, want_cache=True)
        logits = L.unembed_logits(x[:, -1:], params["unembed"])[:, 0]
        return logits, caches

    def decode_step(self, params, batch, caches):
        cfg = self.cfg
        lengths = batch["lengths"].astype(jnp.int32)
        x = L.embed_lookup(params["embed"], batch["token"][:, None],
                           cfg.cdtype)
        pos = jnp.take(params["encdec"]["dec_pos"], lengths, axis=0)
        x = x + pos[:, None, :].astype(x.dtype)
        x, new_caches = encdec.decode_stack(
            cfg, params["encdec"], x, None, positions=lengths[:, None],
            caches=caches, lengths=lengths)
        logits = L.unembed_logits(x, params["unembed"])[:, 0]
        return logits, new_caches

    def input_specs(self, shape: ShapeConfig):
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        frames = jax.ShapeDtypeStruct((b, cfg.n_frames, cfg.d_model),
                                      cfg.cdtype)
        if shape.kind == "train":
            return {**_token_specs(b, s), "frames": frames}
        if shape.kind == "prefill":
            return {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32),
                    "frames": frames}
        return {"token": jax.ShapeDtypeStruct((b,), jnp.int32),
                "lengths": jax.ShapeDtypeStruct((b,), jnp.int32)}

    def input_axes(self, shape: ShapeConfig):
        a = super().input_axes(shape)
        if shape.kind in ("train", "prefill"):
            a["frames"] = ("batch", "frames", "embed")
        return a

    def cache_spec(self, shape: ShapeConfig):
        return encdec.cache_spec(self.cfg, shape.global_batch, shape.seq_len)


_FAMILIES = {
    "dense": DenseLM,
    "moe": MoELM,
    "moe_mla": MLAMoELM,
    "hybrid": ZambaLM,
    "ssm": RWKVLM,
    "encdec": EncDecLM,
    "vlm": VLM,
}


def build_model(cfg: ArchConfig):
    family = cfg.family
    if family == "moe" and cfg.kv_lora_rank:
        family = "moe_mla"
    return _FAMILIES[family](cfg)


def build_model_by_id(arch_id: str):
    return build_model(get_config(arch_id))
