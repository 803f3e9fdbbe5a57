"""Model facade: one object per architecture with a uniform API.

    model = build_model(cfg)
    params = model.init(key)
    loss, aux = model.loss_fn(params, batch)              # training
    logits, cache = model.prefill(params, batch)          # serving prefill
    logits, cache = model.decode_step(params, tok, cache, cache_len)

Batches are dicts:
    decoder LMs:  {"tokens": (B,S), "labels": (B,S)}
    VLM:          + {"patches": (B,P,D)}   (stubbed frontend embeddings)
    audio encdec: {"frames": (B,S_enc,D), "tokens": (B,S_dec), "labels": ...}
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .encdec import (encdec_cache_shapes, encdec_decode_step, encdec_forward,
                     encdec_template)
from .layers import init_from_template, specs_from_template
from .transformer import (decoder_decode_step, decoder_decode_step_paged,
                          decoder_forward, decoder_prefill_chunk,
                          decoder_template, init_cache_shapes,
                          lm_loss, paged_cache_shapes)

__all__ = ["Model", "build_model"]


@dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------- params

    def template(self):
        if self.cfg.family == "encdec":
            return encdec_template(self.cfg)
        return decoder_template(self.cfg)

    def init(self, key, shardings=None):
        """Seeded parameters, built on the devices ``shardings`` names
        (a tree matching ``template()``; None = the default device)."""
        return init_from_template(self.template(), key, shardings=shardings)

    def param_specs(self):
        return specs_from_template(self.template())

    # ------------------------------------------------------------ forward

    def forward(self, params, batch, *, collect_cache=False, remat=None):
        cfg = self.cfg
        if cfg.family == "encdec":
            return encdec_forward(params, cfg, batch["frames"],
                                  batch["tokens"],
                                  collect_cache=collect_cache, remat=remat)
        fe = batch.get("patches") if cfg.family == "vlm" else None
        return decoder_forward(params, cfg, batch["tokens"],
                               frontend_embeds=fe,
                               collect_cache=collect_cache, remat=remat,
                               lengths=batch.get("lengths"))

    def loss_fn(self, params, batch, remat=None):
        """Scalar LM loss (+ router aux)."""
        logits, _, aux = self.forward(params, batch, remat=remat)
        labels = batch["labels"]
        if self.cfg.family == "vlm" and "patches" in batch:
            logits = logits[:, batch["patches"].shape[1]:]
        # next-token shift
        loss = lm_loss(logits[:, :-1], labels[:, 1:],
                       batch.get("loss_mask"))
        return loss + aux, {"lm_loss": loss, "aux_loss": aux}

    # ------------------------------------------------------------ serving

    def prefill(self, params, batch):
        """Returns (last-position logits (B,V), cache dict).

        ``batch`` may carry ``"lengths"`` (B,) int32 true row lengths for
        end-padded token buffers: recurrent families mask the scan so the
        returned state is bit-identical to an unpadded prefill (the engine
        pads to pow2 buckets for a bounded compile set).  Note the
        last-position logits are then pad-position logits — the serving
        engine never uses them (rewind-one-position trick)."""
        logits, cache, _ = self.forward(params, batch, collect_cache=True,
                                        remat=False)
        return logits[:, -1, :], cache

    def decode_step(self, params, token, cache, cache_len):
        """token: (B,1); cache_len: (B,). Returns ((B,V) logits, cache)."""
        cfg = self.cfg
        if cfg.family == "encdec":
            logits, cache = encdec_decode_step(params, cfg, token, cache,
                                               cache_len)
        else:
            logits, cache = decoder_decode_step(params, cfg, token, cache,
                                                cache_len)
        return logits[:, -1, :], cache

    def cache_shapes(self, batch: int, max_len: int, enc_len: int = 0):
        if self.cfg.family == "encdec":
            return encdec_cache_shapes(self.cfg, batch, max_len, enc_len)
        return init_cache_shapes(self.cfg, batch, max_len)

    def init_cache(self, batch: int, max_len: int, enc_len: int = 0):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.cache_shapes(batch, max_len, enc_len))

    # ----------------------------------------------------- paged serving

    @property
    def supports_paged(self) -> bool:
        """Can this model decode through a block-table KV pool?  Every
        decoder family qualifies (SSM state is per-slot, not paged);
        encdec needs its encoder cross-cache and stays dense."""
        return self.cfg.family != "encdec"

    @property
    def supports_chunked_prefill(self) -> bool:
        """Sarathi-style chunk-at-a-time prefill needs attention KV for
        the prefix — SSM/hybrid recurrent state can't replay a chunk."""
        return self.cfg.family in ("dense", "vlm", "moe")

    def paged_cache_shapes(self, n_pages: int, page_size: int,
                           n_slots: int):
        return paged_cache_shapes(self.cfg, n_pages, page_size, n_slots)

    def init_paged_cache(self, n_pages: int, page_size: int, n_slots: int,
                         shardings=None):
        """Zeroed paged cache, built on the devices ``shardings`` names
        (a tree matching ``paged_cache_shapes``; None = default device)."""
        shapes = self.paged_cache_shapes(n_pages, page_size, n_slots)
        return jax.jit(
            lambda: jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                                 shapes),
            out_shardings=shardings)()

    def decode_step_paged(self, params, token, cache, cache_len,
                          block_tables, *, page_size: int):
        """Paged decode step.  token: (B,1); cache_len: (B,);
        block_tables: (B, P) int32.  Returns ((B,V) logits, cache)."""
        logits, cache = decoder_decode_step_paged(
            params, self.cfg, token, cache, cache_len, block_tables,
            page_size=page_size)
        return logits[:, -1, :], cache

    def prefill_chunk(self, params, tokens, past_k, past_v, start):
        """One prefill chunk against the cached prefix; returns the
        chunk's (k, v): (L, 1, C, KV, dh) for the engine to scatter."""
        return decoder_prefill_chunk(params, self.cfg, tokens,
                                     past_k, past_v, start)


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
