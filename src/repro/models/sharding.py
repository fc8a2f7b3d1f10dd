"""Parameter/activation partitioning rules (TP + FSDP + EP).

Axes: "model" carries tensor/expert parallelism; "data" carries batch DP and
FSDP parameter sharding; "pod" (multi-pod mesh) carries pure DP — parameters
are replicated across pods so all TP collectives stay on intra-pod ICI and
only gradient all-reduce crosses the DCN.

Rules are path-pattern driven over the param pytree; any dimension whose size
is not divisible by its mesh axis falls back to replication (DESIGN.md §4
lists the archs this affects: arctic 56 heads, whisper 8 heads, kv<16).
"""

from __future__ import annotations

import re
from typing import Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


# ---------------------------------------------------------------------------
# logical activation constraints (MaxText-style named roles)
# ---------------------------------------------------------------------------
# Roles: "batch" -> (pod,)data axes; "heads"/"vocab"/"expert"/"ffn" -> model
# axis; None/"seq"/other -> unconstrained. Constraints apply only under an
# ambient mesh (jax.set_mesh) and only when the dim divides the axis size, so
# the same model code runs unchanged on CPU tests (no-op) and on the
# production mesh (explicit placement — §Perf iteration 1 showed that leaving
# score tensors to propagation silently replicates heavy attention tensors).

_MODEL_ROLES = ("heads", "vocab", "expert", "ffn")


def model_axis_size() -> int:
    """Size of the ambient mesh's "model" axis (0 when no mesh is set)."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty or "model" not in am.axis_names:
        return 0
    return int(dict(am.shape)["model"])


def logical_constraint(x, *roles):
    am = jax.sharding.get_abstract_mesh()
    if am.empty or "model" not in am.axis_names:
        return x
    sizes = dict(am.shape)
    ba = tuple(a for a in ("pod", "data") if a in am.axis_names)
    ba_size = int(np.prod([sizes[a] for a in ba])) if ba else 1
    assert len(roles) == x.ndim, (roles, x.shape)
    spec = []
    for role, dim in zip(roles, x.shape):
        if role == "batch" and ba and dim % ba_size == 0:
            spec.append(ba)
        elif role in _MODEL_ROLES and dim % sizes["model"] == 0:
            spec.append("model")
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))


# (path regex, spec builder) — builder returns axis names per *trailing* dim
# (the stacked layer dim, when present, is always None).
# "T" = model/tensor axis, "F" = fsdp (data) axis, "." = replicated.
_RULES: list[tuple[str, str]] = [
    (r"embed/tok$", "TF"),
    (r"head$", "FT"),
    (r"(mix|cross)/w[qkv]$", "FT"),
    (r"(mix|cross)/b[qkv]$", "T"),
    (r"(mix|cross)/wo$", "TF"),
    (r"mix/w_dkv$", "F."),          # MLA latent down-proj (small)
    (r"mix/w_krope$", "F."),
    (r"mix/[kv]_up$", ".T"),
    (r"moe/router$", "F."),
    (r"moe/w[ig]$", "TF."),         # (E, D, Fe): EP on experts
    (r"moe/wo$", "T.F"),
    (r"(shared|dense)/w[ig]$", "FT"),
    (r"(shared|dense)/wo$", "TF"),
    (r"mlp/w[ig]$", "FT"),
    (r"mlp/wo$", "TF"),
    (r"mix/in_proj$", "F."),        # mamba2 fused zxBCdt projection
    (r"mix/out_proj$", "TF"),
    (r"mix/w_(gate|rec_in)$", "FT"),
    (r"mix/w_[ri]$", ".T"),
    (r"mix/(lam|conv_b|norm_scale)$", "T"),
    (r"mix/conv_w$", ".T"),
    (r"mix/(A_log|D|dt_bias)$", "."),
    (r"(ln1|ln2|ln_x|final_norm)/(scale|bias)$", "."),
]


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def _spec_for(path: str, shape: tuple[int, ...], mesh: Mesh) -> P:
    tp = mesh.shape["model"]
    fsdp = mesh.shape["data"]
    stacked = bool(re.search(r"segments/\d+/s\d+/|encoder/layers/", path))

    code: Optional[str] = None
    for pat, c in _RULES:
        if re.search(pat, path):
            code = c
            break
    if code is None:
        return P()  # replicate unknowns

    trailing = shape[1:] if stacked else shape
    if len(code) != len(trailing):
        return P()  # rule/shape mismatch -> safe fallback

    axes = []
    for ch, dim in zip(code, trailing):
        if ch == "T" and dim % tp == 0:
            axes.append("model")
        elif ch == "F" and dim % fsdp == 0:
            axes.append("data")
        else:
            axes.append(None)
    if stacked:
        axes = [None] + axes
    return P(*axes)


def param_specs(params_tree, mesh: Mesh):
    """PartitionSpec pytree matching ``params_tree`` (arrays or SDS leaves)."""
    def fn(path, leaf):
        return _spec_for(_path_str(path), tuple(leaf.shape), mesh)
    return jax.tree_util.tree_map_with_path(fn, params_tree)


def param_shardings(params_tree, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), param_specs(params_tree, mesh))


def input_sharding_specs(cfg, specs: dict, mesh: Mesh):
    """PartitionSpecs for model inputs (tokens/targets/embeds/cache)."""
    ba = batch_axes(mesh)
    ba_size = int(np.prod([mesh.shape[a] for a in ba]))

    def bspec(size):
        # shard the batch only when divisible (long_500k has batch 1)
        return ba if size % ba_size == 0 else None

    def leaf_spec(path, leaf):
        p = _path_str(path)
        nd = len(leaf.shape)
        if p.startswith("cache/"):
            return _cache_spec(cfg, p, leaf.shape, mesh)
        if p in ("tokens", "targets"):
            return P(bspec(leaf.shape[0]), None)
        if p == "positions":
            return P(bspec(leaf.shape[0]))
        if p.endswith("embeds"):
            return P(bspec(leaf.shape[0]), None, None) if nd == 3 else P(*([None] * nd))
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(leaf_spec, specs)


def _cache_spec(cfg, path: str, shape, mesh: Mesh) -> P:
    """KV/state caches: batch over data(+pod); heads over model if divisible,
    else the sequence axis over model (distributed-KV decode)."""
    ba = batch_axes(mesh)
    ba_size = int(np.prod([mesh.shape[a] for a in ba]))
    if len(shape) >= 2 and shape[1] % ba_size != 0:
        ba = None  # batch not divisible (long_500k batch=1)
    tp = mesh.shape["model"]
    nd = len(shape)
    # stacked layer dim first, then batch
    if re.search(r"/(k|v|ck|cv)$", path) and nd == 5:   # (L, B, W, G, hd)
        G = shape[3]
        if G % tp == 0:
            return P(None, ba, None, "model", None)
        if shape[2] % tp == 0:
            return P(None, ba, "model", None, None)
        return P(None, ba, None, None, None)
    if re.search(r"/(c|r)$", path) and nd == 4:          # (L, B, S, L_lat)
        if shape[2] % tp == 0:
            return P(None, ba, "model", None)
        return P(None, ba, None, None)
    if re.search(r"/state$", path) and nd == 5:          # (L, B, nh, P, N)
        return P(None, ba, "model" if shape[2] % tp == 0 else None, None, None)
    if re.search(r"/h$", path) and nd == 3:              # (L, B, R)
        return P(None, ba, "model" if shape[2] % tp == 0 else None)
    if re.search(r"/conv$", path) and nd == 4:           # (L, B, K-1, C)
        return P(None, ba, None, "model" if shape[3] % tp == 0 else None)
    return P(*([None] * nd))
