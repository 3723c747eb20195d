"""Work counts from shapes: the operations and bytes a step needs.

Counted from the configuration and the traffic alone, never from the
program, so a count stays the same whatever implements the step.  An
operation is a multiply or an add (a multiply-accumulate is two).
"""

from __future__ import annotations

from bench.dims import Dims

__all__ = ["layer_matmul_flops", "prefill_flops", "decode_flops",
           "request_flops", "head_work"]


def layer_matmul_flops(d: Dims) -> int:
    """Weight-matmul operations of one layer for one token."""
    attn = 2 * d.d_model * (2 * d.n_heads + 2 * d.n_kv) * d.head_dim
    mlp = 2 * (3 if d.act == "silu" else 2) * d.d_model * d.d_ff
    return attn + mlp


def _attn_flops(d: Dims, keys: int) -> int:
    """Scores and value mix of one query against ``keys`` keys, one layer."""
    return 4 * d.n_heads * d.head_dim * keys


def prefill_flops(d: Dims, batch: int, prompt: int) -> int:
    """A causal prefill of ``batch`` prompts of ``prompt`` tokens: every
    layer at every position, each query against the keys up to it, and
    the LM head at the last position only (the one token it serves)."""
    causal_keys = prompt * (prompt + 1) // 2
    per_row = d.n_layers * (prompt * layer_matmul_flops(d)
                            + _attn_flops(d, causal_keys))
    return batch * (per_row + 2 * d.d_model * d.vocab)


def decode_flops(d: Dims, batch: int, pos: int) -> int:
    """One decode step of ``batch`` rows at position ``pos`` (the cache
    then holds ``pos + 1`` keys), the LM head once per token."""
    per_row = d.n_layers * (layer_matmul_flops(d) + _attn_flops(d, pos + 1))
    return batch * (per_row + 2 * d.d_model * d.vocab)


def request_flops(d: Dims, batch: int, prompt: int, gen: int) -> int:
    """Prefill, then ``gen`` decode steps at positions prompt .. prompt+gen-1."""
    return prefill_flops(d, batch, prompt) + sum(
        decode_flops(d, batch, prompt + i) for i in range(gen))


def head_work(batch: int, k: int, v: int, m: int) -> tuple[int, int]:
    """Operations and bytes of one call of the layered head: ``m`` int8
    digit planes of the (k, v) weight, each read once, against a bfloat16
    hidden (batch, k), giving ``m`` float32 resolutions (m, batch, v)."""
    ops = 2 * m * batch * k * v
    nbytes = m * k * v + 2 * batch * k + 4 * m * batch * v
    return ops, nbytes
