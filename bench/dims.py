"""A model configuration file, read into the sizes the benchmark uses.

The files under ``bench/configs/`` keep the public ``config.json`` keys
of the model they name; this module reads them and nothing else, so the
benchmark's work counts, weights and reference all follow the file.
"""

from __future__ import annotations

import dataclasses

__all__ = ["Dims"]


@dataclasses.dataclass(frozen=True)
class Dims:
    name: str
    d_model: int
    d_ff: int
    n_layers: int
    n_heads: int
    n_kv: int
    head_dim: int
    vocab: int
    rope_theta: float
    eps: float
    act: str              # "silu" (SwiGLU) | "gelu" (tanh GELU MLP)
    norm: str             # "rmsnorm" | "layernorm"
    tie: bool             # LM head is the embedding, transposed
    mlp_bias: bool        # the MLP's two projections carry biases

    @classmethod
    def from_config(cls, c: dict) -> "Dims":
        acts = {"silu": "silu", "gelu_pytorch_tanh": "gelu"}
        act = c["hidden_act"]
        if act not in acts:
            raise ValueError(f"{c['name']}: hidden_act {act!r} not one of "
                             f"{sorted(acts)}")
        layer_norm = c.get("norm_type") == "layer_norm"
        heads = c["num_attention_heads"]
        return cls(
            name=c["name"], d_model=c["hidden_size"],
            d_ff=c["intermediate_size"], n_layers=c["num_hidden_layers"],
            n_heads=heads, n_kv=c["num_key_value_heads"],
            head_dim=c.get("head_dim", c["hidden_size"] // heads),
            vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
            eps=float(c["norm_epsilon"] if layer_norm else c["rms_norm_eps"]),
            act=acts[act], norm="layernorm" if layer_norm else "rmsnorm",
            tie=bool(c["tie_word_embeddings"]),
            mlp_bias=bool(c.get("use_bias", False)))
