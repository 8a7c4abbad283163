"""Mesh-aware sharding rules (FSDP + TP + SP + EP).

Conventions (single pod mesh ``(data=16, model=16)``; multi-pod adds a
leading ``pod`` axis that composes with ``data`` for FSDP/DP):

  * batch dims of activations  -> (pod, data)
  * attention heads / FFN hidden / vocab / experts -> model  (TP / EP)
  * parameters are 2-D sharded: TP axis over ``model`` AND the other large
    dim over ``fsdp`` = (pod, data), so per-chip bytes scale 1/(total chips)
  * sequence-parallel: activations in norm/residual regions may shard the
    sequence dim over ``model``

Every rule is divisibility-guarded: an axis is only applied when the dim is
divisible by the mesh axis size, so the same model code serves all ten
architectures (24-head models simply leave heads replicated).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def current_mesh():
    """The mesh set by ``jax.set_mesh``, or None off-mesh.

    Returned as the ``AbstractMesh`` (axis names and sizes) because that is
    what stays readable inside ``jax.jit`` tracing, where ``constrain`` runs.
    The legacy ``with mesh:`` context is not consulted: enter meshes with
    ``jax.set_mesh``."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def fsdp_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    return fsdp_axes(mesh)


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def resolve(mesh: Mesh, spec_entries: tuple, dims: tuple[int, ...]) -> P:
    """Build a PartitionSpec, dropping axes whose dim is not divisible."""
    out = []
    for entry, dim in zip(spec_entries, dims):
        if entry is None:
            out.append(None)
            continue
        axes = tuple(a for a in ((entry,) if isinstance(entry, str) else entry)
                     if a in mesh.axis_names)
        if not axes or dim % _axis_size(mesh, axes) != 0:
            out.append(None)
        elif len(axes) == 1:
            out.append(axes[0])
        else:
            out.append(axes)
    return P(*out)


def constrain(x: jax.Array, *entries) -> jax.Array:
    """with_sharding_constraint with divisibility-guarded logical entries.

    Entries use physical axis names ('data', 'model', 'pod') or the logical
    markers 'fsdp' / 'batch' which expand to (pod, data).
    """
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = resolve(mesh, _expand(mesh, entries), x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _expand(mesh: Mesh, entries) -> tuple:
    """Logical markers 'fsdp' / 'batch' -> the mesh's (pod, data) axes."""
    return tuple(fsdp_axes(mesh) if e in ("fsdp", "batch") else e
                 for e in entries)


def named_sharding(mesh: Mesh, *entries, dims: tuple[int, ...]) -> NamedSharding:
    return NamedSharding(mesh, resolve(mesh, _expand(mesh, entries), dims))


def per_device(fn, operands, in_entries, out_entries, out_dims):
    """``fn(*operands)``, run on each device's shard under the ambient mesh.

    XLA cannot partition a Pallas (Mosaic) kernel, so under a mesh a kernel
    call goes through ``jax.shard_map``.  ``in_entries`` holds one tuple of
    logical entries per operand and ``out_entries`` one for the result of
    shape ``out_dims`` (entries as for :func:`constrain`).  An axis that
    does not divide its dim is dropped, so that dim reaches the kernel
    whole.  Off-mesh this is a plain call."""
    mesh = current_mesh()
    if mesh is None:
        return fn(*operands)
    in_specs = tuple(resolve(mesh, _expand(mesh, e), x.shape)
                     for e, x in zip(in_entries, operands))
    out_spec = resolve(mesh, _expand(mesh, out_entries), out_dims)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs, out_specs=out_spec,
                         check_vma=False)(*operands)


def constrain_priority(x: jax.Array, batch_dims: int, candidates: list[int],
                       axis: str = "model") -> jax.Array:
    """Constrain ``x`` sharding ``axis`` onto the FIRST candidate dim whose
    size divides the axis (e.g. decode KV: prefer kv-heads, fall back to
    d_head).  Leading ``batch_dims`` dims shard over (pod, data)."""
    mesh = current_mesh()
    if mesh is None or axis not in mesh.axis_names:
        return x
    size = mesh.shape[axis]
    entries: list = [fsdp_axes(mesh) if i < batch_dims else None
                     for i in range(x.ndim)]
    for dim in candidates:
        if x.shape[dim] % size == 0:
            entries[dim] = axis
            break
    spec = resolve(mesh, tuple(entries), x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def param_sharding(params, mesh: Mesh, spec_fn) -> dict:
    """Map a pytree of (path, array/ShapeDtypeStruct) -> NamedSharding via
    ``spec_fn(path, shape) -> tuple of entries``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    shardings = []
    for path, leaf in flat:
        entries = spec_fn(path, leaf.shape)
        shardings.append(named_sharding(mesh, *entries, dims=leaf.shape))
    return jax.tree_util.tree_unflatten(treedef, shardings)


def tree_shardings(tree, mesh: Mesh, spec_fn):
    """NamedSharding pytree for an arbitrary tree (caches, batches) via the
    same ``spec_fn(path, shape)`` protocol as :func:`param_sharding`."""
    return param_sharding(tree, mesh, spec_fn)


def make_cache_spec_fn(mesh: Mesh, cfg=None):
    """path/shape -> spec entries for the KV-cache pytree (dense AND paged).

    Dense K/V shard kv-heads over 'model' when divisible, else the sequence
    dim; paged pools shard the per-token kv-head axis the same way (pages
    and the slot->page table themselves are never split -- admission
    rewrites the table host-side and scatter/gather must see whole pages).
    Used by the serve engine for cache ``out_shardings`` and by the dry-run
    lowering; ``cfg`` is accepted for signature stability but the rules are
    shape/path-driven.
    """
    del cfg
    msize = mesh.shape["model"] if "model" in mesh.axis_names else 1

    def entries(path, shape):
        name = None
        for k in reversed(path):
            kk = getattr(k, "key", None)
            if isinstance(kk, str):
                name = kk
                break
        names = [getattr(k, "key", None) for k in path]
        lead = 1 if "layers" in names else 0   # stacked per-layer caches
        core = shape[lead:]
        pre = (None,) * lead

        if isinstance(name, str) and name.endswith("_pages"):
            # page pools (pool, page_size, ...feat): shard the kv-head axis
            # of K/V payload pools; MLA latent/rope pools (3-d) replicate --
            # their feature dim contracts through the up-projection
            if len(core) == 4 and core[2] % msize == 0:
                return pre + (None, None, "model", None)
            return pre + (None,) * len(core)
        if isinstance(name, str) and name.endswith("_scales"):
            # per-token-per-head scale pools mirror their payload pool
            if len(core) == 3 and core[2] % msize == 0:
                return pre + (None, None, "model")
            return pre + (None,) * len(core)
        if name == "page_table":
            # owned by the host-side allocator mirror; every shard needs the
            # full slot->page mapping for gather/scatter index computation
            return (None,) * len(shape)
        if name in ("k", "v") and len(core) == 4:
            _, s, kvh, dh = core
            if kvh % msize == 0:
                return pre + ("batch", None, "model", None)
            if s % msize == 0:
                # sequence-sharded cache: scores come out S-sharded, softmax
                # reduces only (B,H) scalars cross-shard, PV psums (B,H,dv)
                # -- measured far cheaper than gathering the cache or
                # psum-ing dh-sharded scores (§Perf iteration 5)
                return pre + ("batch", "model", None, None)
            return pre + ("batch", None, None, None)
        if name == "c" and len(core) == 3:                 # MLA latent
            s = core[1]
            if s % msize == 0:
                return pre + ("batch", "model", None)
            return pre + ("batch", None, "model")
        if name == "k_pe":
            s = core[1]
            if s % msize == 0:
                return pre + ("batch", "model", None)
            return pre + ("batch", None, None)
        if name is not None and name.startswith("conv") and len(core) == 3:
            return pre + ("batch", None, "model")
        if name == "ssm" and len(core) == 3:               # mamba1 (B, di, N)
            return pre + ("batch", "model", None)
        if name == "ssm" and len(core) == 4:               # mamba2 (B, H, P, N)
            return pre + ("batch", "model", None, None)
        if name in ("len", "pos") and core:
            # per-slot position counters live with their slot's cache shard
            return pre + ("batch",) + (None,) * (len(core) - 1)
        if not core:
            return (None,) * len(shape)
        return pre + ("batch",) + (None,) * (len(core) - 1)

    return entries
