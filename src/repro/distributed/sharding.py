"""Logical-axis sharding rules (MaxText-style) → mesh PartitionSpecs.

Parameters declare *logical* axes in their schema; a rules table maps them to
mesh axes per parallelism configuration. The engine enforces two invariants
GSPMD requires:

  * a mesh axis may appear at most once per spec (first logical axis wins;
    e.g. MoE weights [experts, embed, mlp] give "model" to experts, so the
    per-expert mlp dim falls back to replicated);
  * a dim is only sharded if its size divides the mesh axis extent
    (e.g. vocab=50280 on a 16-way model axis stays replicated rather than
    forcing padding).

Batch/sequence sharding for inputs and caches is chosen adaptively per shape
cell (decode batch=1 cells shard sequence/heads instead of batch).
"""

from __future__ import annotations

import contextlib
import contextvars
import math

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import common

# default FSDP(data) × TP(model) rules; pods replicate params (pure DP).
DEFAULT_RULES: dict[str, str | tuple[str, ...] | None] = {
    "vocab": "model",
    "embed": "data",        # FSDP axis
    "embed_out": None,
    "q_heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "experts": "model",
    "layers": None,
    None: None,
}

# beyond-baseline: shard parameters over pods too (FSDP across the DCI).
POD_FSDP_RULES = dict(DEFAULT_RULES, embed=("pod", "data"))

# §Perf plan for small models: replicate params, shard batch over EVERY
# mesh axis (TP on a 384-wide model wastes the model axis on redundant
# compute; pure DP puts all 256 chips on distinct data).
PURE_DP_RULES = {k: None for k in DEFAULT_RULES}


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return math.prod(mesh.shape[a] for a in axes)


def spec_for_axes(logical_axes: tuple, mesh: Mesh, shape: tuple,
                  rules: dict) -> P:
    used: set[str] = set()
    out = []
    for dim, name in zip(shape, logical_axes):
        mesh_axes = rules.get(name, None)
        if mesh_axes is None:
            out.append(None)
            continue
        if isinstance(mesh_axes, str):
            mesh_axes = (mesh_axes,)
        mesh_axes = tuple(a for a in mesh_axes if a in mesh.shape
                          and a not in used)
        if not mesh_axes or dim % _axis_size(mesh, mesh_axes) != 0:
            out.append(None)
            continue
        used.update(mesh_axes)
        out.append(mesh_axes if len(mesh_axes) > 1 else mesh_axes[0])
    return P(*out)


def param_specs(schema: dict, mesh: Mesh, rules: dict | None = None):
    """PartitionSpec pytree for a parameter schema."""
    rules = rules or DEFAULT_RULES
    axes_tree = common.logical_axes_tree(schema)
    abstract = common.abstract_params(schema)
    return jax.tree.map(
        lambda ax, arr: spec_for_axes(ax, mesh, arr.shape, rules),
        axes_tree, abstract, is_leaf=lambda x: isinstance(x, tuple))


def param_shardings(schema: dict, mesh: Mesh, rules: dict | None = None):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(schema, mesh, rules))


# ------------------------------------------------- activation constraints --

# Default logical→mesh rules for activations. Verified necessity: without
# the head constraint, GSPMD loses the head sharding through the flash
# attention reshapes and every chip computes ALL heads (16× attention flops
# in the qwen-0.5b dry-run baseline).
ACT_RULES_DEFAULT: dict[str, str | tuple[str, ...] | None] = {
    "act_batch": ("pod", "data"),
    "act_seq": None,
    "act_heads": "model",
    "act_mlp": "model",
    "act_experts": "model",
    "act_embed": None,
    # sequence parallelism on the residual stream (cfg.sp_residual)
    "act_res_seq": "model",
}

_ACT_CTX: contextvars.ContextVar = contextvars.ContextVar(
    "repro_act_sharding", default=None)


@contextlib.contextmanager
def activation_sharding(mesh: Mesh, rules: dict | None = None):
    """Enable activation sharding constraints for model code traced inside."""
    token = _ACT_CTX.set((mesh, rules or ACT_RULES_DEFAULT))
    try:
        yield
    finally:
        _ACT_CTX.reset(token)


def shard_act(x: jax.Array, *names: str | None) -> jax.Array:
    """Constrain an activation's sharding by logical dim names (no-op when
    no activation_sharding context is active, e.g. single-device tests)."""
    ctx = _ACT_CTX.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    if len(names) != x.ndim:
        return x
    spec = spec_for_axes(tuple(names), mesh, x.shape, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ------------------------------------------------------------ inputs -------

def batch_axes(mesh: Mesh, global_batch: int,
               axes: tuple[str, ...] = ("pod", "data")) -> tuple[str, ...]:
    """Largest prefix of ``axes`` that divides the global batch."""
    cand = [a for a in axes if a in mesh.shape]
    chosen: list[str] = []
    for a in cand:
        if global_batch % _axis_size(mesh, tuple(chosen) + (a,)) == 0:
            chosen.append(a)
    return tuple(chosen)


def data_batch_spec(mesh: Mesh, global_batch: int, ndim: int,
                    axes: tuple[str, ...] = ("pod", "data")) -> P:
    """Spec for a [B, ...] input array: batch over ``axes`` when it
    divides, otherwise replicated."""
    ba = batch_axes(mesh, global_batch, axes)
    lead = ba if len(ba) > 1 else (ba[0] if ba else None)
    return P(lead, *([None] * (ndim - 1)))


def batch_shardings(batch_struct: dict, mesh: Mesh, global_batch: int,
                    axes: tuple[str, ...] = ("pod", "data")):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, data_batch_spec(mesh, global_batch,
                                                      len(s.shape), axes)),
        batch_struct)


# ------------------------------------------------------------ caches -------

def cache_spec_for(struct: jax.ShapeDtypeStruct, mesh: Mesh,
                   global_batch: int, *, stacked: int = 1) -> P:
    """Sharding for one per-slot cache leaf (paged POOL leaves and block
    tables take the dedicated branches in ``serve_cache_shardings``).

    Leaves are (after optional leading layer-stack dims):
      cross-attn KV [B, Lm, KV, DH]   -> batch over (pod,data) if divisible,
                                         else Lm over (pod,data); heads over
                                         model if divisible, else head_dim.
      SSD state   [B, H, N, P]        -> batch, then H over model.
      conv state  [B, W, C]           -> batch, C over model.
      lengths     [B]                 -> batch.
    """
    shape = struct.shape
    lead = stacked
    dims: list = [None] * len(shape)
    model = mesh.shape.get("model", 1)
    ba = batch_axes(mesh, global_batch)
    b_idx = lead
    if ba and shape[b_idx] % _axis_size(mesh, ba) == 0:
        dims[b_idx] = ba if len(ba) > 1 else ba[0]
        seq_shardable = False
    else:
        seq_shardable = True

    rest = list(range(lead + 1, len(shape)))
    if rest and seq_shardable and len(shape) >= lead + 2:
        # shard the sequence dim instead (long-context, batch=1 cells)
        s_idx = lead + 1
        sa = tuple(a for a in ("pod", "data") if a in mesh.shape)
        if sa and shape[s_idx] % _axis_size(mesh, sa) == 0:
            dims[s_idx] = sa if len(sa) > 1 else sa[0]
            rest = [i for i in rest if i != s_idx]

    # give "model" to the first remaining dim it divides; for 4-dim KV
    # caches prefer the kv-head dim (lead+2), falling back to head_dim.
    order = [lead + 2, lead + 3] if len(shape) - lead == 4 else rest
    for i in order:
        if i < len(shape) and dims[i] is None and model > 1 \
                and shape[i] % model == 0:
            dims[i] = "model"
            break
    return P(*dims)


def _kv_heads(cfg) -> int:
    """KV heads of the model's GQA pools (hybrid: its shared block's)."""
    return (cfg.hybrid.num_kv_heads if cfg.family == "hybrid"
            else cfg.num_kv_heads)


def serve_cache_shardings(cfg, cache_struct, mesh: Mesh, global_batch: int):
    """Sharding pytree for a model's stacked decode caches.

    The number of leading layer-stack dims is family/path dependent
    (hybrid's per-segment mamba states carry (n_seg, seg, ...) stacks).
    Paged-cache leaves get dedicated treatment: the shared block pools
    [stack, NB, bs, ...] must never shard their block/position axes
    (block addressing is indirect — any rank may own any slot's block),
    so they replicate except for a model split on a divisible feature
    dim; block tables and lengths shard over batch only.

    GQA payload pools are lane-dense, [stack, NB, bs, H * D]: their
    feature dim gets "model" only where the kv-head count H divides by
    the axis, so every rank holds whole heads.

    Scale-array rule (quantized pools, repro.quant): the per-block scale
    tiles ("kscale"/"vscale" [stack, NB, bs, H], MLA's "c_kv_scale"/
    "k_rope_scale" [stack, NB, bs]) are pools too (POOL_KEYS) and take
    the same branch — block axis over the data axes when divisible,
    never the within-block position axis, and the head dim gets "model"
    exactly when the value pool's heads do, so a rank always holds a
    block's payload and its scales together.
    """
    from jax.tree_util import tree_map_with_path

    from repro.models.paged import POOL_KEYS

    def one(path, s):
        lead = 1
        if cfg.family == "hybrid":
            names = {str(getattr(p, "key", "")) for p in path}
            if "mamba" in names:
                lead = 2
        name = str(getattr(path[-1], "key", path[-1]))
        if name in POOL_KEYS:
            # [stack, NB, bs, *feat]: partition the pool's BLOCK axis over
            # the data axes when it divides (block addressing is indirect,
            # GSPMD turns the table gather into collectives; per-chip KV
            # memory stays 1/data of the pool — dryrun pads NB to make
            # this divide, see paged.padded_num_blocks). Never shard the
            # within-block position axis.
            dims: list = [None] * len(s.shape)
            ba = tuple(a for a in ("pod", "data") if a in mesh.shape)
            if ba and s.shape[lead] % _axis_size(mesh, ba) == 0:
                dims[lead] = ba if len(ba) > 1 else ba[0]
            model = mesh.shape.get("model", 1)
            # GQA payloads [stack, NB, bs, hkv * d] split whole heads only
            heads = _kv_heads(cfg) if name in ("kpool", "vpool") else None
            for i in range(lead + 2, len(s.shape)):
                if model > 1 and s.shape[i] % model == 0 and (
                        heads is None or heads % model == 0):
                    dims[i] = "model"
                    break
            return NamedSharding(mesh, P(*dims))
        if name == "block_table":
            dims = [None] * len(s.shape)
            ba = batch_axes(mesh, global_batch)
            if ba and s.shape[lead] % _axis_size(mesh, ba) == 0:
                dims[lead] = ba if len(ba) > 1 else ba[0]
            return NamedSharding(mesh, P(*dims))
        return NamedSharding(mesh, cache_spec_for(s, mesh, global_batch,
                                                  stacked=lead))
    return tree_map_with_path(one, cache_struct)
