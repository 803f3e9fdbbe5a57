"""The system under test for a ``model_type: qwen2`` configuration: the
program's model config and parameter tree, built from the benchmark's
own seeded weights (``refs/qwen2.py``) so that the program makes none
of what the reference reads."""

from __future__ import annotations

__all__ = ["model_config", "vocab_rows", "params"]


def model_config(cfg: dict):
    from repro.models.config import ModelConfig
    D, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=D, n_heads=H,
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], head_dim=D // H,
        activation="swiglu", qkv_bias=True, rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)))


def vocab_rows(cfg: dict) -> int:
    """Rows of the program's embedding (its vocabulary, padded)."""
    return model_config(cfg).padded_vocab


def params(w: dict) -> dict:
    """The program's parameter tree over the same arrays (no copies)."""
    tree = {
        "embed": w["embed"],
        "final_norm": {"scale": w["final_norm"]},
        "layers": {
            "ln1": {"scale": w["ln1"]}, "ln2": {"scale": w["ln2"]},
            "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo",
                                       "bq", "bk", "bv")},
            "mlp": {"w_gate": w["w_gate"], "w_in": w["w_up"],
                    "w_out": w["w_down"]},
        },
    }
    if "lm_head" in w:
        tree["lm_head"] = w["lm_head"]
    return tree
