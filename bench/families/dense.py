"""The dense decoder family: grouped-query attention with rotary
positions, and either RMSNorm with a SwiGLU MLP and an untied head (Llama,
Yi) or LayerNorm with a tanh-GELU MLP and a tied head (StarCoder2).

Its sizes are ``bench.dims.Dims``, its weights ``bench.weights`` and its
work counts ``bench.work``; its plain reference is
``bench/reference/dense.py``.
"""

from __future__ import annotations

from bench import weights, work
from bench.dims import Dims

__all__ = ["dims", "model_config", "make_params", "request_flops"]

#: the whole tree from the seed, layers stacked, in one jitted program
make_params = weights.make_params
#: prefill, then ``gen`` decode steps, counted from the shapes
request_flops = work.request_flops


def dims(config: dict) -> Dims:
    """The sizes a configuration file gives."""
    return Dims.from_config(config)


def model_config(d: Dims):
    """The program's configuration of the model in a configuration file."""
    from repro.configs.base import AttentionConfig, ModelConfig
    return ModelConfig(
        name=d.name, family="dense", num_layers=d.n_layers,
        d_model=d.d_model, d_ff=d.d_ff, vocab_size=d.vocab,
        attention=AttentionConfig(num_heads=d.n_heads, num_kv_heads=d.n_kv,
                                  head_dim=d.head_dim,
                                  rope_theta=d.rope_theta),
        activation=d.act, norm=d.norm, tie_embeddings=d.tie)
