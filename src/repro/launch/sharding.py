"""Sharding rules: parameter / optimizer-state / cache / batch PartitionSpecs.

MaxText-style logical layout on a ("pod"?, "data", "model") mesh:

* batch            -> ("pod", "data")      (pods are pure DP; see fault.py)
* vocab / heads / experts / ffn / d_inner  -> "model"   (tensor parallel)
* d_model (embed) on weight matrices       -> "data"    (ZeRO-3 / FSDP)
* scanned-layer leading axis               -> replicated (scan carries it)
* optimizer state mirrors its parameter (factored Adafactor states inherit
  the parameter's spec minus the reduced dimension)

Rules are keyed on the *leaf name* (the last key in the parameter path) and
the leaf's rank, so they apply uniformly to every architecture in the zoo.
pjit rejects non-divisible argument shardings, so ``fix_spec`` relocates a
mesh axis to a dividing dim (8 KV heads can't split 16 ways -> shard
head_dim instead) or drops it; every fallback is visible in the dry-run's
sharding dump.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import batch_axes

__all__ = ["param_specs", "opt_state_specs", "batch_specs", "cache_specs_tree",
           "named", "spec_bytes_per_device"]

# body specs EXCLUDING any leading scanned-layer axis (prepended if present)
_FSDP = "data"
_TP = "model"

_BODY_RULES: dict[tuple[str, int], tuple] = {
    # attention
    ("wq", 3): (_FSDP, _TP, None),
    ("wk", 3): (_FSDP, _TP, None),
    ("wv", 3): (_FSDP, _TP, None),
    ("wo", 3): (_TP, None, _FSDP),
    # dense / shared-expert MLPs
    ("w_gate", 2): (_FSDP, _TP),
    ("w_up", 2): (_FSDP, _TP),
    ("w_down", 2): (_TP, _FSDP),
    ("w_fc", 2): (_FSDP, _TP),
    ("w_proj", 2): (_TP, _FSDP),
    ("b_fc", 1): (_TP,),
    ("b_proj", 1): (None,),
    # MoE experts (leading E axis; "we_*" names are the routed experts)
    ("we_gate", 3): (_TP, _FSDP, None),
    ("we_up", 3): (_TP, _FSDP, None),
    ("we_down", 3): (_TP, None, _FSDP),
    ("router", 2): (_FSDP, None),
    # Mamba2 (split per-stream projections; see models/ssm.py)
    ("gate_proj", 2): (_FSDP, _TP),
    ("x_proj", 2): (_FSDP, _TP),
    # B/C/dt projections are tiny (d_model x 128 / x H); TP-sharding their
    # outputs makes the SSD score einsum a psum -- replicate instead.
    ("B_proj", 2): (_FSDP, None),
    ("C_proj", 2): (_FSDP, None),
    ("dt_proj", 2): (_FSDP, None),
    ("out_proj", 2): (_TP, _FSDP),
    ("conv_x", 2): (None, _TP),
    ("conv_x_b", 1): (_TP,),
    ("conv_B", 2): (None, _TP),
    ("conv_B_b", 1): (_TP,),
    ("conv_C", 2): (None, _TP),
    ("conv_C_b", 1): (_TP,),
    ("conv_w", 2): (None, _TP),
    ("conv_b", 1): (_TP,),
    ("A_log", 1): (_TP,),
    ("D", 1): (_TP,),
    ("dt_bias", 1): (_TP,),
    ("norm_scale", 1): (_TP,),
    # RG-LRU
    ("in_gelu", 2): (_FSDP, _TP),
    ("in_rnn", 2): (_FSDP, _TP),
    ("w_a", 2): (None, _TP),
    ("w_x", 2): (None, _TP),
    ("b_a", 1): (_TP,),
    ("b_x", 1): (_TP,),
    ("Lambda", 1): (_TP,),
    ("out", 2): (_TP, _FSDP),
    # norms: tiny, replicated
    ("scale", 1): (None,),
    ("bias", 1): (None,),
}

_TOP_RULES: dict[str, tuple] = {
    "embed": (_TP, _FSDP),       # (V, D)
    "lm_head": (_FSDP, _TP),     # (D, V)
}


def _leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def _axis_size(mesh: Mesh, ax) -> int:
    axes = ax if isinstance(ax, tuple) else (ax,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def fix_spec(shape: tuple, spec: tuple, mesh: Mesh, *,
             relocate: bool = True) -> P:
    """Make a proposed spec legal for ``shape`` on ``mesh``.

    pjit requires every *argument* dimension to divide evenly by its mesh
    axes (GSPMD pads intermediates, not inputs).  For each named axis whose
    proposed dim does not divide, try to relocate it to a later (then
    earlier) unassigned dim that does divide — e.g. 8 KV heads cannot shard
    over a 16-way "model" axis, but head_dim=128 can, so
    (..., "model", None) becomes (..., None, "model").  If no dim fits, the
    axis is dropped (replicated) — visible honestly in the roofline.
    """
    spec = tuple(spec)[: len(shape)]
    spec = spec + (None,) * (len(shape) - len(spec))
    out: list = [None] * len(shape)
    for i, ax in enumerate(spec):
        if ax is None:
            continue
        size = _axis_size(mesh, ax)
        candidates = (list(range(i, len(shape))) + list(range(i))
                      if relocate else [i])
        for j in candidates:
            if out[j] is None and spec[j] in (None, ax) \
                    and shape[j] % size == 0:
                out[j] = ax
                break
        # else: dropped (replicated)
    return P(*out)


# Attention projections must NOT relocate their TP axis to head_dim when
# the heads don't divide: dh-sharded q/k makes every score matmul a psum of
# an S x S tensor (measured: ~2 TB/device on llama3 pre-fix).  Dropping TP
# (heads replicated across "model", FSDP kept on d_model) is strictly
# better; the redundant attention compute shows up honestly in the HLO
# FLOPs term.
_NO_RELOCATE = {"wq", "wk", "wv", "wo"}


def _spec_for(path, leaf, mesh: Mesh) -> P:
    name = _leaf_name(path)
    ndim = len(leaf.shape)
    reloc = name not in _NO_RELOCATE
    if name in _TOP_RULES and ndim == len(_TOP_RULES[name]):
        return fix_spec(leaf.shape, _TOP_RULES[name], mesh, relocate=reloc)
    if (name, ndim) in _BODY_RULES:
        return fix_spec(leaf.shape, _BODY_RULES[(name, ndim)], mesh,
                        relocate=reloc)
    if (name, ndim - 1) in _BODY_RULES:  # scanned: leading repeats axis
        return fix_spec(leaf.shape,
                        (None,) + _BODY_RULES[(name, ndim - 1)], mesh,
                        relocate=reloc)
    return P()  # replicate anything unmatched (scalars, counters, ...)


def param_specs(params_shapes: Any, mesh: Mesh,
                profile: str = "tp_fsdp") -> Any:
    """PartitionSpec pytree matching a params (shape) pytree.

    profile "serve" drops the FSDP axis (weights stay TP-sharded,
    replicated over data): serving must not re-gather weights per token.
    """
    flat, treedef = jax.tree.flatten_with_path(params_shapes)
    specs = [_spec_for(p, l, mesh) for p, l in flat]
    if profile == "serve":
        specs = [P(*(None if ax == _FSDP else ax for ax in tuple(sp)))
                 for sp in specs]
    return treedef.unflatten(specs)


def opt_state_specs(opt_shapes: Any, pspecs: Any, mesh: Mesh) -> Any:
    """Optimizer-state specs.

    m/v mirror their parameter; Adafactor's factored "vr" (param minus last
    dim) and "vc" (param minus second-to-last) drop that entry of the spec;
    scalars (step/gnorm/lr) replicate.
    """
    pflat, _ = jax.tree.flatten_with_path(pspecs,
                                      is_leaf=lambda x: isinstance(x, P))
    by_path = {tuple(_leaf_name_seq(p)): s for p, s in pflat}

    def spec_of(path, leaf):
        names = _leaf_name_seq(path)
        if not names or names[0] in ("step", "gnorm", "lr"):
            return P()
        kind = names[0]              # "m" | "v" | ...
        rest = tuple(names[1:])
        if kind in ("m", "v") and rest and rest[-1] in ("vr", "vc", "v"):
            sub, rest = rest[-1], rest[:-1]
        else:
            sub = None
        pspec = by_path.get(rest)
        if pspec is None:
            return P()
        spec = tuple(pspec)
        spec = spec + (None,) * (len(_shape_of(leaf)) - len(spec)) \
            if len(spec) < len(_shape_of(leaf)) else spec
        if sub == "vr":
            spec = spec[:-1]
        elif sub == "vc":
            spec = spec[:-2] + spec[-1:]
        if len(spec) != len(_shape_of(leaf)):
            spec = spec[: len(_shape_of(leaf))]
        return fix_spec(_shape_of(leaf), spec, mesh)

    flat, treedef = jax.tree.flatten_with_path(opt_shapes)
    return treedef.unflatten([spec_of(p, l) for p, l in flat])


def _shape_of(leaf):
    return getattr(leaf, "shape", ())


def _leaf_name_seq(path) -> list[str]:
    return [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]


def batch_specs(batch_shapes: Any, mesh: Mesh,
                profile: str = "tp_fsdp") -> Any:
    """Shard dim 0 of every batch leaf over the batch axes; scalars replicate."""
    baxes = batch_axes(mesh)
    if profile == "fsdp":  # pure-DP: the model axis also carries batch
        baxes = tuple(a for a in ("pod", "data", "model")
                      if a in mesh.axis_names)

    def spec(leaf):
        shape = _shape_of(leaf)
        if len(shape) == 0:
            return P()
        return fix_spec(shape, (baxes,) + (None,) * (len(shape) - 1),
                        mesh, relocate=False)

    return jax.tree.map(spec, batch_shapes)


def cache_specs_tree(cache_shapes: Any, mesh: Mesh) -> Any:
    """Decode caches: (reps, B, ...) leaves -> batch on dim 1, heads/model
    dims heuristically on the axis whose name matches, else replicated.

    Cache layouts (see transformer.init_cache):
      k/v   (reps, B, S, n_kv, Dh) -> (None, batch, None, "model", None)
      pos   (reps, B, W)           -> (None, batch, None)
      conv  (reps, B, K, C)        -> (None, batch, None, "model")
      state (reps, B, H, P, N)     -> (None, batch, "model", None, None)
      h     (reps, B, R)           -> (None, batch, "model")
    Distinguishing k/v from state: state is fp32 and named "state".
    """
    baxes = batch_axes(mesh)
    flat, treedef = jax.tree.flatten_with_path(cache_shapes)

    def _first_legal(shape, candidates):
        """First candidate whose named axes all survive fix_spec."""
        best = None
        for prop in candidates:
            want = sum(1 for a in prop if a is not None)
            fixed = fix_spec(shape, prop, mesh, relocate=False)
            got = sum(1 for a in tuple(fixed) if a is not None)
            if best is None:
                best = fixed
            if got == want:
                return fixed
        return best

    def spec(path, leaf):
        name = _leaf_name_seq(path)[-1]
        nd = len(_shape_of(leaf))
        shape = _shape_of(leaf)
        if (name in ("k", "v") or nd == 5) and nd == 5:
            # KV caches (reps, B, S, n_kv, Dh): head-parallel when the KV
            # heads divide the TP axis, else context-parallel on S
            # (flash-decoding style) so the cache never replicates.
            return _first_legal(shape, [(None, baxes, None, _TP, None),
                                        (None, baxes, _TP, None, None)])
        if name == "state":
            prop = (None, baxes, _TP, None, None)
        elif name == "conv":
            prop = (None, baxes, None, _TP)
        elif name == "h":
            prop = (None, baxes, _TP)
        elif name == "pos":
            prop = (None, baxes, None)
        else:
            prop = (None,) * nd
        return fix_spec(shape, prop, mesh, relocate=False)

    return treedef.unflatten([spec(p, l) for p, l in flat])


def named(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def spec_bytes_per_device(shapes: Any, specs: Any, mesh: Mesh) -> int:
    """Estimated per-device bytes for a (shape, spec) pytree pair."""
    total = 0
    for leaf, spec in zip(jax.tree.leaves(shapes),
                          jax.tree.leaves(specs,
                                          is_leaf=lambda x: isinstance(x, P))):
        shape = list(leaf.shape)
        for i, ax in enumerate(tuple(spec)[: len(shape)]):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            div = int(np.prod([mesh.shape[a] for a in axes]))
            shape[i] = int(np.ceil(shape[i] / div))
        total += int(np.prod(shape)) * leaf.dtype.itemsize
    return total
