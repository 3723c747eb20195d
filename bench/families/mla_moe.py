"""The latent-attention mixture-of-experts family (DeepSeek-V2): latent
attention with YaRN rotary positions, leading dense SwiGLU layers, then
expert layers of softmax-routed SwiGLU experts beside a shared expert;
RMSNorm and an untied head.

One chip holds a contiguous share of each expert layer's experts, the
first ``n_routed_experts`` of them (an expert-parallel deployment cut to
chip 0): the router keeps its published width (``published`` in the
configuration file), and what the other chips' experts would add is left
out here and in the reference alike.

Weights are drawn from the seed one leaf at a time, as ``bench.weights``
draws the dense family's: every leaf has its own key, folded from the
seed, the layer and the leaf's index, so the reference
(``bench/reference/mla_moe.py``) regenerates any layer alone with
``layer_params``.  Scales keep activations of order one: a projection is
normal with variance ``1 / fan_in``; an RMSNorm gain is ``1 + N(0,
0.1^2)``, stored as its offset from 1 as the program keeps it.  The
router alone is drawn wider, its logits' standard deviation
``router_std`` (``assumed`` in the configuration file): at 1 the softmax
over 64 experts is nearly flat, the top-6 gates sum to about 0.36 and
the held experts move the logits by little more than bfloat16 does; at 3
they sum to about 0.86, the top pick about 0.48.

The harness runs this file as a module it does not register, so the
dataclass below must not take its annotations as strings (no ``from
__future__ import annotations``).
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp

__all__ = ["Dims", "dims", "model_config", "make_params", "layer_params",
           "embed", "head", "final_norm", "request_flops", "expert_work"]

_EMBED, _HEAD, _FINAL = 1 << 20, (1 << 20) + 1, (1 << 20) + 2


@dataclasses.dataclass(frozen=True)
class Dims:
    name: str
    d_model: int
    vocab: int
    n_layers: int
    n_dense: int          # leading dense layers
    d_ff: int             # their SwiGLU width
    n_heads: int
    kv_rank: int          # the cached latent's width
    qk_nope: int
    qk_rope: int
    v_dim: int
    rope_theta: float
    rope_factor: float    # YaRN
    rope_orig: int
    beta_fast: float      # YaRN's ramp ends, which the program fixes
    beta_slow: float
    mscale_all_dim: float
    eps: float
    n_experts: int        # the router's width: every expert of the layer
    n_held: int           # the experts this chip holds: 0 .. n_held-1
    top_k: int
    d_expert: int
    d_shared: int         # the shared experts, run as one SwiGLU
    router_std: float     # the router logits' standard deviation


def _require(c: dict, key: str, want) -> None:
    if c.get(key) != want:
        raise ValueError(f"{c['name']}: {key} {c.get(key)!r}; the "
                         f"mla_moe family serves only {want!r}")


def dims(config: dict) -> Dims:
    """The sizes a configuration file gives."""
    c = config
    for key, want in (("hidden_act", "silu"), ("scoring_func", "softmax"),
                      ("topk_method", "greedy"), ("q_lora_rank", None),
                      ("moe_layer_freq", 1), ("n_group", 1),
                      ("topk_group", 1), ("attention_bias", False),
                      ("tie_word_embeddings", False),
                      ("routed_scaling_factor", 1),
                      ("norm_topk_prob", False)):
        _require(c, key, want)
    rs = c["rope_scaling"]
    if (rs.get("type") != "yarn" or rs["mscale"] != rs["mscale_all_dim"]
            or (rs["beta_fast"], rs["beta_slow"]) != (32, 1)):
        # with mscale = mscale_all_dim YaRN leaves cos and sin unscaled,
        # as the program does; the program's ramp ends are 32 and 1
        raise ValueError(f"{c['name']}: rope_scaling {rs!r} is not yarn "
                         f"with mscale = mscale_all_dim and betas 32, 1")
    return Dims(
        name=c["name"], d_model=c["hidden_size"], vocab=c["vocab_size"],
        n_layers=c["num_hidden_layers"], n_dense=c["first_k_dense_replace"],
        d_ff=c["intermediate_size"], n_heads=c["num_attention_heads"],
        kv_rank=c["kv_lora_rank"], qk_nope=c["qk_nope_head_dim"],
        qk_rope=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
        rope_theta=float(c["rope_theta"]), rope_factor=float(rs["factor"]),
        rope_orig=int(rs["original_max_position_embeddings"]),
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale_all_dim=float(rs["mscale_all_dim"]),
        eps=float(c["rms_norm_eps"]),
        n_experts=c["published"]["n_routed_experts"],
        n_held=c["n_routed_experts"], top_k=c["num_experts_per_tok"],
        d_expert=c["moe_intermediate_size"],
        d_shared=c["n_shared_experts"] * c["moe_intermediate_size"],
        router_std=float(c["assumed"]["router_std"]))


def model_config(d: Dims):
    """The program's configuration of the model in a configuration file."""
    from repro.configs.base import AttentionConfig, ModelConfig, MoEConfig
    return ModelConfig(
        name=d.name, family="moe", num_layers=d.n_layers, d_model=d.d_model,
        d_ff=d.d_ff, vocab_size=d.vocab,
        attention=AttentionConfig(
            num_heads=d.n_heads, num_kv_heads=d.n_heads,
            head_dim=d.qk_nope + d.qk_rope, rope_theta=d.rope_theta,
            kv_lora_rank=d.kv_rank, qk_nope_head_dim=d.qk_nope,
            qk_rope_head_dim=d.qk_rope, v_head_dim=d.v_dim,
            rope_factor=d.rope_factor, rope_original_max_pos=d.rope_orig,
            rope_mscale_all_dim=d.mscale_all_dim),
        moe=MoEConfig(num_experts=d.n_experts, top_k=d.top_k,
                      d_ff_expert=d.d_expert, d_ff_shared=d.d_shared,
                      first_dense_layers=d.n_dense,
                      held_experts=(0, d.n_held)),
        activation="silu", norm="rmsnorm", tie_embeddings=False)


# ---------------------------------------------------------------------------
# Weights from the seed
# ---------------------------------------------------------------------------

def _normal(key_data, path, shape, std):
    k = jax.random.wrap_key_data(key_data)
    for p in path:
        k = jax.random.fold_in(k, p)
    return jax.random.normal(k, shape, jnp.float32) * std


def _gain(key_data, path, n):
    return {"scale": _normal(key_data, path, (n,), 0.1)}


def layer_params(key_data, d: Dims, i, moe: bool) -> dict:
    """Layer ``i``'s weights (``i`` may be traced); ``moe`` says whether
    it is an expert layer or one of the leading dense ones."""
    D, H, r = d.d_model, d.n_heads, d.kv_rank
    w = lambda j, shape, fan_in: _normal(key_data, (i, j), shape,
                                         fan_in ** -0.5)
    p = {
        "ln1": _gain(key_data, (i, 0), D),
        "attn": {"wq": w(1, (D, H, d.qk_nope + d.qk_rope), D),
                 "wkv_a": w(2, (D, r + d.qk_rope), D),
                 "kv_norm": _gain(key_data, (i, 3), r),
                 "wkv_b": w(4, (r, H, d.qk_nope + d.v_dim), r),
                 "wo": w(5, (H, d.v_dim, D), H * d.v_dim)},
        "ln2": _gain(key_data, (i, 6), D),
    }
    if not moe:
        p["ffn"] = {"w_gate": w(7, (D, d.d_ff), D),
                    "w_up": w(8, (D, d.d_ff), D),
                    "w_down": w(9, (d.d_ff, D), d.d_ff)}
        return p
    E, F, S = d.n_held, d.d_expert, d.d_shared
    p["ffn"] = {
        "router": _normal(key_data, (i, 10), (D, d.n_experts),
                          d.router_std * D ** -0.5),
        "we_gate": w(11, (E, D, F), D), "we_up": w(12, (E, D, F), D),
        "we_down": w(13, (E, F, D), F),
        "shared": {"w_gate": w(14, (D, S), D), "w_up": w(15, (D, S), D),
                   "w_down": w(16, (S, D), S)},
    }
    return p


def embed(key_data, d: Dims):
    """The (vocab, d_model) embedding."""
    return _normal(key_data, (_EMBED,), (d.vocab, d.d_model), 0.02)


def head(key_data, d: Dims):
    """The untied LM head, (d_model, vocab)."""
    return _normal(key_data, (_HEAD,), (d.d_model, d.vocab),
                   d.d_model ** -0.5)


def final_norm(key_data, d: Dims):
    return _gain(key_data, (_FINAL,), d.d_model)


@functools.partial(jax.jit, static_argnums=1)
def make_params(key_data, d: Dims) -> dict:
    """The whole tree, each group's layers stacked, in one program."""
    def group(lo, hi, moe):
        return [jax.vmap(lambda i: layer_params(key_data, d, i, moe))(
            jnp.arange(lo, hi))]
    return {"embed": embed(key_data, d), "final_norm": final_norm(key_data, d),
            "lm_head": head(key_data, d),
            "groups": [group(0, d.n_dense, False),
                       group(d.n_dense, d.n_layers, True)]}


# ---------------------------------------------------------------------------
# Work counts from shapes
# ---------------------------------------------------------------------------

def _held_pairs(d: Dims, tokens: int) -> int:
    """(token, expert) pairs the held experts see, as routing spreads
    ``tokens`` evenly: top_k of n_experts picks, n_held of them here."""
    return tokens * d.top_k * d.n_held // d.n_experts


def _layer_flops(d: Dims, tokens: int, moe: bool) -> int:
    """Weight products of one layer over ``tokens`` tokens, bar the
    latent's up-projection, which prefill and decode count apart."""
    D, H = d.d_model, d.n_heads
    attn = 2 * tokens * D * (H * (d.qk_nope + d.qk_rope) + d.kv_rank
                             + d.qk_rope + H * d.v_dim)
    if not moe:
        return attn + 6 * tokens * D * d.d_ff
    return (attn + 2 * tokens * D * d.n_experts
            + 6 * _held_pairs(d, tokens) * D * d.d_expert
            + 6 * tokens * D * d.d_shared)


def _layers(d: Dims, tokens: int) -> int:
    return (d.n_dense * _layer_flops(d, tokens, False)
            + (d.n_layers - d.n_dense) * _layer_flops(d, tokens, True))


def prefill_flops(d: Dims, batch: int, prompt: int) -> int:
    """A causal prefill with the latent expanded into per-head keys and
    values, each query against the keys up to it, and the LM head at the
    last position only."""
    H, T = d.n_heads, batch * prompt
    keys = batch * prompt * (prompt + 1) // 2
    expand = 2 * T * d.kv_rank * H * (d.qk_nope + d.v_dim)
    attn = 2 * keys * H * (d.qk_nope + d.qk_rope + d.v_dim)
    return (_layers(d, T) + d.n_layers * (expand + attn)
            + 2 * batch * d.d_model * d.vocab)


def decode_flops(d: Dims, batch: int, pos: int) -> int:
    """One decode step at position ``pos`` with the up-projection
    absorbed: the query into the latent space, scores over ``pos + 1``
    cached latents, their weighted sum back through the value half."""
    H, keys = d.n_heads, pos + 1
    absorb = 2 * batch * H * d.kv_rank * (d.qk_nope + d.v_dim)
    attn = 2 * batch * keys * H * (2 * d.kv_rank + d.qk_rope)
    return (_layers(d, batch) + d.n_layers * (absorb + attn)
            + 2 * batch * d.d_model * d.vocab)


def request_flops(d: Dims, batch: int, prompt: int, gen: int) -> int:
    """Prefill, then ``gen`` decode steps at positions prompt ..
    prompt+gen-1."""
    return prefill_flops(d, batch, prompt) + sum(
        decode_flops(d, batch, prompt + i) for i in range(gen))


def expert_work(d: Dims, tokens: int) -> tuple[int, int]:
    """Operations and bytes of one expert layer's grouped products over
    ``tokens`` tokens: the held pairs' three SwiGLU products, each held
    expert's bfloat16 weights read once, and the pairs' bfloat16 rows in
    and out."""
    pairs = _held_pairs(d, tokens)
    ops = 6 * pairs * d.d_model * d.d_expert
    nbytes = 2 * (3 * d.n_held * d.d_model * d.d_expert
                  + 2 * pairs * d.d_model)
    return ops, nbytes
