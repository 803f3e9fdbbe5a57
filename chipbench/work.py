"""Operations and bytes a model's work requires, from its configuration's
shapes (Hugging Face keys), and the chip's peaks by ``device_kind``.

What is counted is the work the algorithm needs, whatever implements it:
matmul FLOPs (2 per multiply-add) of every projection at each token, the
attention's two products over the keys a token sees, and the logits only
where a token is sampled.  A decode call reads every weight once, the
embedding rows of its tokens, and the KV of each lane's true context;
an MoE layer reads the weights of the experts its tokens route to, at
most min(E, tokens x top-k) of them.
"""

from __future__ import annotations

import json
from pathlib import Path

__all__ = ["Shapes", "peaks", "param_count", "kv_bytes_per_token",
           "token_flops", "prompt_flops", "decode_call"]

_PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip; a device the table lacks is an
    error, never a default."""
    table = json.loads(_PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r} in {_PEAKS.name}")
    return table[device_kind]


class Shapes:
    def __init__(self, cfg: dict):
        self.L = cfg["num_hidden_layers"]
        self.D = cfg["hidden_size"]
        self.H = cfg["num_attention_heads"]
        self.KV = cfg["num_key_value_heads"]
        self.dh = cfg.get("head_dim") or self.D // self.H
        self.V = cfg["vocab_size"]
        self.tied = bool(cfg.get("tie_word_embeddings", False))
        self.bias = cfg.get("model_type") == "qwen2" \
            or bool(cfg.get("attention_bias", False))
        self.E = int(cfg.get("num_experts", 0))
        self.K = int(cfg.get("num_experts_per_tok", 0))
        self.F = cfg["intermediate_size"]
        self.qk_norm = cfg.get("model_type") == "olmoe"

    @property
    def attn_params(self) -> int:
        D, H, KV, dh = self.D, self.H, self.KV, self.dh
        return D * H * dh + 2 * D * KV * dh + H * dh * D

    @property
    def expert_params(self) -> int:
        return 3 * self.D * self.F          # gate, up, down (SwiGLU)

    @property
    def ffn_active_params(self) -> int:
        """Matmul parameters of one layer's FFN that one token uses."""
        if self.E:
            return self.K * self.expert_params + self.D * self.E  # + router
        return self.expert_params

    @property
    def small_bytes(self) -> int:
        """float32 norm scales and biases, all layers and the final norm."""
        n = self.L * 2 * self.D + self.D
        if self.bias:
            n += self.L * (self.H + 2 * self.KV) * self.dh
        if self.qk_norm:
            n += self.L * (self.H + self.KV) * self.dh
        return 4 * n


def param_count(cfg: dict) -> int:
    s = Shapes(cfg)
    ffn = (s.E * s.expert_params + s.D * s.E) if s.E else s.expert_params
    per_layer = s.attn_params + ffn
    n = s.L * per_layer + s.V * s.D * (1 if s.tied else 2)
    return n + s.small_bytes // 4


def kv_bytes_per_token(cfg: dict, dtype_bytes: int = 2) -> int:
    s = Shapes(cfg)
    return 2 * s.L * s.KV * s.dh * dtype_bytes


def token_flops(cfg: dict, pos: int, logits: bool = True) -> int:
    """FLOPs of one token at position ``pos`` (it attends to pos + 1
    keys, itself included)."""
    s = Shapes(cfg)
    f = 2 * s.L * (s.attn_params + s.ffn_active_params)
    f += 4 * s.L * s.H * s.dh * (pos + 1)
    if logits:
        f += 2 * s.D * s.V
    return f


def prompt_flops(cfg: dict, lo: int, hi: int) -> int:
    """FLOPs of prompt positions [lo, hi) through every layer, no
    logits (only a sampled position needs them)."""
    if hi <= lo:
        return 0
    s = Shapes(cfg)
    n = hi - lo
    f = 2 * s.L * (s.attn_params + s.ffn_active_params) * n
    keys = (lo + 1 + hi) * n // 2                # sum of pos + 1 over [lo, hi)
    return f + 4 * s.L * s.H * s.dh * keys


def decode_call(cfg: dict, positions: list[int],
                weight_bytes: int = 2) -> tuple[int, int]:
    """(FLOPs, bytes) one decode call requires for lanes at
    ``positions`` (each samples one token)."""
    s = Shapes(cfg)
    b = len(positions)
    flops = sum(token_flops(cfg, p) for p in positions)
    if s.E:
        held = min(s.E, b * s.K)
        ffn = held * s.expert_params + s.D * s.E
    else:
        ffn = s.expert_params
    mats = s.L * (s.attn_params + ffn) + s.D * s.V       # output head once
    nbytes = weight_bytes * mats + s.small_bytes
    nbytes += b * s.D * weight_bytes                       # embedding rows
    # each lane reads its cached context and writes its new position
    nbytes += kv_bytes_per_token(cfg) * sum(p + 1 for p in positions)
    return flops, nbytes
