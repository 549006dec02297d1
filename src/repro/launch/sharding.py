"""Sharding rules: one place mapping every parameter / activation / cache
leaf to a PartitionSpec over the production mesh.

Mesh axes: ``("data", "model")`` single-pod, ``("pod", "data", "model")``
multi-pod; the pod axis extends data parallelism.  Rules:

  batch dims            -> ("pod","data")      (DP; ZeRO-style state shard)
  attention heads / FFN hidden / experts / vocab -> "model"  (TP / EP)
  KV-cache: heads over "model" when divisible, else sequence (SP) —
            the long_500k cells shard the 524k-token cache by sequence.

Every rule degrades gracefully: an axis is applied only if the dim is
divisible by the mesh axis size (e.g. 8 KV heads on a 16-wide model axis
fall back to sequence sharding).
"""

from __future__ import annotations

import contextlib
import re
import threading
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_STATE = threading.local()


def current_mesh() -> Optional[Mesh]:
    return getattr(_STATE, "mesh", None)


@contextlib.contextmanager
def use_mesh(mesh: Mesh):
    """Make sharding constraints active (dry-run / real runs enter this)."""
    prev = getattr(_STATE, "mesh", None)
    _STATE.mesh = mesh
    try:
        with jax.set_mesh(mesh):
            yield mesh
    finally:
        _STATE.mesh = prev


def _axis_size(mesh: Mesh, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, tuple):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return int(mesh.shape[axis])


def _fit(mesh: Mesh, shape, spec_axes) -> P:
    """Drop spec axes that do not divide the corresponding dim."""
    fitted = []
    for dim, axis in zip(shape, spec_axes):
        if axis is not None and dim % _axis_size(mesh, axis) == 0 and dim > 0:
            fitted.append(axis)
        else:
            fitted.append(None)
    return P(*fitted)


def batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


# ======================================================================
# activations
# ======================================================================
def logical_shard(x: jax.Array, kind: str) -> jax.Array:
    """Constraint activations inside model code; no-op without a mesh."""
    mesh = current_mesh()
    if mesh is None:
        return x
    b = batch_axes(mesh)
    if kind == "act":  # (B, S, D)
        spec = _fit(mesh, x.shape, (b, None, None))
    elif kind == "logits":  # (B, S, V)
        spec = _fit(mesh, x.shape, (b, None, "model"))
    else:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


# ======================================================================
# parameters
# ======================================================================
_PARAM_RULES: list[tuple[str, tuple]] = [
    # (path regex, spec template aligned from the RIGHT; left dims pad None).
    # Two-axis sharding: "model" = tensor/expert parallel, "data" = FSDP /
    # ZeRO-3 — without the data axis a 671B-param arch cannot reside on a
    # 16-GB-HBM chip at 16-way TP (EXPERIMENTS.md §Dry-run).
    (r"experts/w_(gate|up)$", ("model", "data", None)),  # (L,E,D,F)
    (r"experts/w_down$", ("model", None, "data")),       # (L,E,F,D)
    (r"router$", (None, None)),                          # replicated (tiny)
    (r"(wq|wk|wv|w_gate|w_up|w_qkv|w_in|w_dt|wq_b|wk_b|wv_b|w_if|wq_a|wkv_a)$",
     ("data", "model")),                                 # (..., D, F)
    (r"(wo|w_down|w_out)$", ("model", "data")),          # (..., F, D)
    (r"r_gates$", ("data", "model")),
    (r"a_log$", ("model", None)),                        # (L, di, n)
    (r"d_skip$", ("model",)),
    (r"w_conv$", (None, "model")),
    (r"(b_up|bq|bk|bv)$", ("model",)),
    (r"(b_down|b_if|norm.*|d_skip)$", (None,)),
    (r"^embed$", ("model", "data")),                     # (V, D)
    (r"^lm_head$", ("data", "model")),                   # (D, V)
    (r"^frontend_proj$", ("data", "model")),
    (r"^final_norm$", (None,)),
]


def param_pspec(path: str, shape, mesh: Mesh, *, inference: bool = False) -> P:
    for pattern, tail in _PARAM_RULES:
        if re.search(pattern, path):
            if inference:
                # weight-stationary serving: no FSDP axis (no per-step
                # gathers); experts spread over (model x data) whole-expert
                if "experts" in path:
                    tail = (("model", "data"), None, None)
                else:
                    tail = tuple(None if a == "data" else a for a in tail)
            full = (None,) * max(0, len(shape) - len(tail)) + tuple(
                tail[-len(shape):] if len(tail) > len(shape) else tail
            )
            return _fit(mesh, shape, full)
    return _fit(mesh, shape, (None,) * len(shape))


def _path_str(path) -> str:
    parts = []
    for p in path:
        key = getattr(p, "key", None)
        parts.append(str(key) if key is not None else str(p))
    return "/".join(parts)


def params_shardings(params_abstract, mesh: Mesh, *, inference: bool = False):
    """NamedShardings for a (possibly abstract) param pytree."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh,
            param_pspec(_path_str(path), leaf.shape, mesh, inference=inference),
        ),
        params_abstract,
    )


# ======================================================================
# decode caches / states / optimizer
# ======================================================================
def cache_pspec(shape, mesh: Mesh) -> P:
    """Shard a decode-cache leaf.

    Cache leaves are stacked per layer: (L, B, ...rest) — e.g. GQA KV
    (L, B, H, S, D), MLA latent (L, B, S, r), Mamba state (L, B, di, n).
    Rule: L replicated; B -> data when divisible; the first remaining dim
    divisible by the model axis -> model (heads for GQA, sequence for MLA —
    that IS sequence parallelism for the long-context cells, di for SSM
    states)."""
    b = batch_axes(mesh)
    spec: list = [None] * len(shape)
    if len(shape) >= 2 and shape[1] % _axis_size(mesh, b) == 0:
        spec[1] = b
    for dim in range(2, len(shape)):
        if shape[dim] % _axis_size(mesh, "model") == 0:
            spec[dim] = "model"
            break
    return P(*spec)


def cache_shardings(cache_abstract, mesh: Mesh):
    return jax.tree.map(
        lambda leaf: NamedSharding(mesh, cache_pspec(leaf.shape, mesh)),
        cache_abstract,
    )


def batch_shardings(batch_abstract, mesh: Mesh):
    b = batch_axes(mesh)
    return jax.tree.map(
        lambda leaf: NamedSharding(
            mesh, _fit(mesh, leaf.shape, (b,) + (None,) * (len(leaf.shape) - 1))
        ),
        batch_abstract,
    )


def _is_int8_moment(x) -> bool:
    return isinstance(x, dict) and "q" in x and "scale" in x


def opt_state_shardings(opt_abstract, params_abstract, mesh: Mesh):
    """Shardings for the AdamW state tree.

    fp32/bf16 moments mirror their parameter's sharding; int8 blockwise
    moments are flat (n_blocks, block) tensors sharded over ALL mesh axes
    on the block dim (fully flat ZeRO sharding).
    """
    param_sh = params_shardings(params_abstract, mesh)

    def mom(m_leaf, p_sh):
        if _is_int8_moment(m_leaf):
            # q keeps the parameter's dims (last padded to the quant block);
            # scale swaps the last dim for n_blocks — both inherit the
            # parameter's PartitionSpec so no resharding happens in-update.
            q_shape = m_leaf["q"].shape
            base = tuple(p_sh.spec) + (None,) * (len(q_shape) - len(p_sh.spec))
            return {
                "q": NamedSharding(mesh, _fit(mesh, q_shape, base)),
                "scale": NamedSharding(
                    mesh,
                    _fit(mesh, m_leaf["scale"].shape, base[:-1] + (None,)),
                ),
            }
        return p_sh

    out = {
        "step": NamedSharding(mesh, P()),
        "m": jax.tree.map(mom, opt_abstract["m"], param_sh,
                          is_leaf=_is_int8_moment),
        "v": jax.tree.map(mom, opt_abstract["v"], param_sh,
                          is_leaf=_is_int8_moment),
    }
    if "ef" in opt_abstract:  # error-feedback residuals follow params
        out["ef"] = param_sh
    return out
