"""Sharding assignments for every dry-run input: params (FSDP + tensor
parallel), optimizer state, batches, and decode caches.

Cache layout reminders (leaves carry a leading scan-repeat dim R):
  attn KVCache : k/v (R, B, S, Kv, hd), pos (R, B, S)
  xattn        : mk/mv (R, B, M, H, hd)
  mamba        : conv (R, B, d_conv-1, d_inner), ssm (R, B, d_inner, d_state)
  rwkv         : S (R, B, H, hd, hd), x_tm/x_cm (R, B, d)

Decode caches shard batch over the data axes; the KV sequence dim shards
over 'model' (sequence-sharded cache) because GQA KV heads (8) do not divide
the 16-way model axis — this is what makes decode_32k fit per-chip HBM
(DESIGN §6).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.launch.mesh import dp_axes
from repro.models.attention import KVCache, PagedKVCache
from repro.sharding import rules


def _ns(mesh, *spec):
    return NamedSharding(mesh, P(*spec))


def _axis_size(mesh, axes) -> int:
    return int(np.prod([mesh.shape[a] for a in axes])) if axes else 1


def _maybe(mesh, dim, axes):
    """axes if dim divisible by their product else None (replicate)."""
    if not axes:
        return None
    return (axes if len(axes) > 1 else axes[0]) \
        if dim % _axis_size(mesh, axes) == 0 else None


def param_shardings(params, mesh: Mesh, *, fsdp: bool = True):
    """fsdp=False keeps params tensor-parallel only (replicated over data):
    the right choice for decode, where a per-step FSDP all-gather would put
    the whole parameter footprint on the ICI every step (§Perf pair B)."""
    return rules.param_shardings(params, mesh,
                                 fsdp_axes=dp_axes(mesh) if fsdp else ())


def serving_param_shardings(params, cfg, mesh: Mesh):
    """Execution-safe tensor-parallel shardings for the serving engines.

    The dry-run rules shard attention projection outputs over 'model'
    whenever the flattened ``heads * head_dim`` axis divides. EXECUTING
    that layout is only safe when the split lands on whole heads: a chunk
    that cuts inside ``head_dim`` reshapes the sharding onto RoPE's
    rotation axis, and that layout splits the rotation pairs across
    devices. Q/K/V projections whose head count does not divide the
    model axis are therefore replicated; everything else follows the
    rules.
    """
    pspecs = rules.param_pspecs(params, mesh, fsdp_axes=())
    model = int(dict(mesh.shape).get(rules.MODEL, 1))
    heads = {"wq": int(getattr(cfg, "n_heads", 1) or 1),
             "wk": int(getattr(cfg, "n_kv_heads", 0)
                       or getattr(cfg, "n_heads", 1) or 1)}
    heads["wv"] = heads["wk"]

    def one(path, spec):
        names = rules._path_names(path)
        parent = names[-2] if len(names) >= 2 else ""
        if parent in heads and heads[parent] % model:
            spec = P()
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, pspecs)


def opt_shardings(opt_state, params, mesh: Mesh):
    pspec = rules.param_pspecs(params, mesh, fsdp_axes=dp_axes(mesh))
    mu = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspec)
    nu = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspec)
    return type(opt_state)(step=_ns(mesh), mu=mu, nu=nu)


def batch_shardings(batch, mesh: Mesh):
    dp = dp_axes(mesh)

    def one(leaf):
        if leaf.ndim == 0:
            return _ns(mesh)
        b = _maybe(mesh, leaf.shape[0], dp)
        return NamedSharding(mesh, P(b, *((None,) * (leaf.ndim - 1))))

    return jax.tree_util.tree_map(one, batch)


def _shard_one_axis(mesh, shape, axis, axes):
    """NamedSharding partitioning exactly one axis (when divisible)."""
    spec = [None] * len(shape)
    spec[axis] = _maybe(mesh, shape[axis], axes)
    return NamedSharding(mesh, P(*spec))


def serving_state_shardings(gstate, mesh: Mesh):
    """Best-effort NamedShardings for a serving ``GroupedState`` (the
    sharded ``StreamingEngine``'s committed-input layout).

    Slot-parallel serving: every per-group ``SessionState`` leaf leads
    with the group's SLOT axis, which shards over the data axes whenever
    the group's slot count divides them — the engine enforces
    divisibility, so the per-slot decode state is genuinely partitioned
    and shard ``s`` owns its slots end to end. The shared cache follows
    the dry-run shardings' ``_maybe`` divisibility contract: paged pools
    shard their PAGE axis (the engine sizes ``n_pages`` divisible by the
    shard count, so the contiguous per-shard page segments of
    ``device_page_plan`` land one segment per data shard), dense KV rows
    shard when the row count divides, and the tiny block tables (plus any
    leaf that does not divide) replicate — replication is always correct
    under SPMD, it just spends interconnect instead of memory."""
    dp = dp_axes(mesh)
    repl = _ns(mesh)

    def group_leaf(leaf):
        if getattr(leaf, "ndim", 0) == 0:
            return repl
        return _shard_one_axis(mesh, leaf.shape, 0, dp)

    def cache_node(node):
        if isinstance(node, PagedKVCache):
            # trailing dims are (pages, ps, Kv * hd) / (pages, ps); a
            # leading scan-repeat dim may or may not be present
            pool = _shard_one_axis(mesh, node.k_pool.shape,
                                   node.k_pool.ndim - 3, dp)
            return PagedKVCache(
                k_pool=pool, v_pool=pool,
                pos=_shard_one_axis(mesh, node.pos.shape,
                                    node.pos.ndim - 2, dp),
                block_tables=repl)
        if isinstance(node, KVCache):
            # trailing dims are (B, S, Kv, hd) / (B, S)
            kv = _shard_one_axis(mesh, node.k.shape, node.k.ndim - 4, dp)
            return KVCache(k=kv, v=kv,
                           pos=_shard_one_axis(mesh, node.pos.shape,
                                               node.pos.ndim - 2, dp))
        return jax.tree_util.tree_map(lambda x: repl, node)

    groups = tuple(jax.tree_util.tree_map(group_leaf, gs)
                   for gs in gstate.groups)
    cache = jax.tree_util.tree_map(
        cache_node, gstate.cache,
        is_leaf=lambda x: isinstance(x, (PagedKVCache, KVCache)))
    return type(gstate)(groups=groups, cache=cache)


def cache_shardings(cache, cfg: ModelConfig, mesh: Mesh):
    """Per-pattern-position cache shardings (tuple aligned with the cache)."""
    dp = dp_axes(mesh)
    out = []
    for kind, c in zip(cfg.layer_pattern, cache):
        if kind == "attn":
            B, S = c.k.shape[1], c.k.shape[2]
            b = _maybe(mesh, B, dp)
            s = _maybe(mesh, S, ("model",))
            kv = _ns(mesh, None, b, s, None, None)
            out.append(KVCache(k=kv, v=kv, pos=_ns(mesh, None, b, s)))
        elif kind == "xattn":
            B, M = c["mk"].shape[1], c["mk"].shape[2]
            b = _maybe(mesh, B, dp)
            h = _maybe(mesh, c["mk"].shape[3], ("model",))
            out.append({"mk": _ns(mesh, None, b, None, h, None),
                        "mv": _ns(mesh, None, b, None, h, None)})
        elif kind == "mamba":
            B = c["conv"].shape[1]
            b = _maybe(mesh, B, dp)
            di = _maybe(mesh, c["ssm"].shape[2], ("model",))
            out.append({"conv": _ns(mesh, None, b, None, di),
                        "ssm": _ns(mesh, None, b, di, None)})
        elif kind == "rwkv":
            B = c["S"].shape[1]
            b = _maybe(mesh, B, dp)
            h = _maybe(mesh, c["S"].shape[2], ("model",))
            out.append({"S": _ns(mesh, None, b, h, None, None),
                        "x_tm": _ns(mesh, None, b, None),
                        "x_cm": _ns(mesh, None, b, None)})
        else:
            raise ValueError(kind)
    return tuple(out)
