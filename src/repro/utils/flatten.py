"""Flat-buffer view of a client-state pytree (DESIGN.md §7).

The fused client loop runs H local steps per round on buffers shaped
``(M, n_total)`` — every params/momentum/D leaf reshaped and concatenated into
one contiguous fp32 buffer per client — so the whole optimizer update is ONE
Pallas pass per local step instead of one launch per leaf.  ``FlatLayout``
records the leaf order, shapes, sizes and offsets of that view so the tree can
be reconstructed bit-exactly at the sync barrier (flatten at round start,
unflatten only at sync).

``ShardFlatLayout`` is the model-/FSDP-sharded counterpart: the single global
flat axis cannot follow per-leaf shardings (GSPMD would reshard the whole
client state every local step), so on sharded plans each device flattens only
its LOCAL leaf shards into an fp32 ``(M, n_local)`` block and the global flat
buffer is the shard-major concatenation of those blocks, sharded over the
plan's model/FSDP axes.  Flatten/unflatten run inside ``shard_map`` so no
collective ever touches the flat buffers; ``ShardedFlatPlan`` bundles the
layout with the mesh/client axes for the engine's fused fast path.

Flatten/unflatten are pure reshape+concatenate / slice+reshape — values are
never touched, which is what makes the flat path bit-identical to the tree
path (pinned in tests/test_fused_step.py and tests/test_fused_sharded.py).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.utils.tree import tree_paths


@dataclasses.dataclass(frozen=True)
class FlatLayout:
    """Layout of a pytree flattened into one trailing ``(n_total,)`` axis.

    Built from a *single-replica* tree (arrays or ShapeDtypeStructs, no
    leading client dim); ``flatten``/``unflatten`` then accept trees whose
    leaves carry ``batch_dims`` extra leading axes (the client dim M in the
    engine) which are preserved as leading axes of the flat buffer.
    """
    treedef: jax.tree_util.PyTreeDef
    paths: tuple          # '/'-joined key path per leaf, flatten order
    shapes: tuple         # single-replica shape per leaf
    sizes: tuple          # element count per leaf
    offsets: tuple        # start offset of each leaf in the flat axis
    n_total: int

    @classmethod
    def for_tree(cls, tree, batch_dims: int = 0) -> "FlatLayout":
        """Derive the layout; ``batch_dims`` leading axes are ignored."""
        paths, shapes, sizes, offsets = [], [], [], []
        off = 0
        for path, leaf in tree_paths(tree):
            shape = tuple(leaf.shape[batch_dims:])
            size = int(np.prod(shape)) if shape else 1
            paths.append(path)
            shapes.append(shape)
            sizes.append(size)
            offsets.append(off)
            off += size
        return cls(treedef=jax.tree.structure(tree), paths=tuple(paths),
                   shapes=tuple(shapes), sizes=tuple(sizes),
                   offsets=tuple(offsets), n_total=off)

    def flatten(self, tree, batch_dims: int = 0):
        """Tree with ``batch_dims`` leading axes -> fp32 ``(*batch, n_total)``."""
        leaves = jax.tree.leaves(tree)
        flat = [l.reshape(l.shape[:batch_dims] + (-1,)).astype(jnp.float32)
                for l in leaves]
        return jnp.concatenate(flat, axis=-1)

    def unflatten(self, buf, batch_dims: int = 0):
        """``(*batch, n_total)`` -> the tree (leaves cast back per-layout fp32
        — the fast path only engages for fp32 state, so this is exact)."""
        batch = buf.shape[:batch_dims]
        leaves = [buf[..., o:o + s].reshape(batch + shp)
                  for o, s, shp in zip(self.offsets, self.sizes, self.shapes)]
        return jax.tree.unflatten(self.treedef, leaves)

    def describe(self) -> dict:
        """JSON-able summary for BuiltStep meta / dry-run artifacts."""
        return {
            "n_total": self.n_total,
            "leaves": [
                {"path": p, "shape": list(s), "size": sz, "offset": o}
                for p, s, sz, o in zip(self.paths, self.shapes, self.sizes,
                                       self.offsets)
            ],
        }


def all_float32(tree) -> bool:
    """True iff every leaf is fp32 — the fused fast path's dtype gate."""
    return all(l.dtype == jnp.float32 for l in jax.tree.leaves(tree))


# --------------------------------------------------------------------------- #
# shard-local flat view (model-/FSDP-sharded plans; DESIGN.md §7)
# --------------------------------------------------------------------------- #


def _entry_axes(entry):
    """PartitionSpec entry -> tuple of mesh-axis names (major first)."""
    if entry is None:
        return ()
    return tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)


@dataclasses.dataclass(frozen=True)
class ShardFlatLayout:
    """Per-shard flat view of a single-replica pytree sharded over ``axes``.

    Built at trace time from the plan's NamedShardings (their PartitionSpecs
    + the mesh axis sizes): for each leaf and each dim, the dim is *split*
    when its spec shards it over a subset of ``axes`` whose extent divides it;
    otherwise — uneven extents (dim % extent ∈ {1, …, extent−1}) and leaves
    smaller than one shard included — that dim falls back to *replicated in
    every shard block*, which is exactly what GSPMD does with such leaves on
    the tree path (each device holds and updates a full copy), so the fused
    step stays bit-identical with zero extra memory per device.

    The global flat buffer is the SHARD-MAJOR concatenation of the per-shard
    local blocks: shape ``(*batch, n_shards · n_local)``, flat axis sharded
    ``P(axes)``.  Each device's resident chunk is precisely the flat view of
    its local leaf shards, so flatten / the fused step / unflatten all run
    inside ``shard_map`` with in_specs == out_specs == the storage shardings:
    no resharding collective can appear (pinned in tests/test_fused_sharded.py).
    """
    local: FlatLayout                 # layout of ONE shard's local blocks
    axes: Tuple[str, ...]             # shard (model/FSDP) axes, major first
    axis_sizes: Tuple[int, ...]       # mesh extent per axis
    specs: tuple                      # per-leaf effective inner PartitionSpec
    global_shapes: tuple              # per-leaf single-replica global shape
    split: tuple                      # per-leaf: any dim actually sharded
    uneven: tuple                     # per-leaf: replicated by uneven fallback

    @property
    def n_shards(self) -> int:
        n = 1
        for s in self.axis_sizes:
            n *= s
        return n

    @property
    def n_local(self) -> int:
        return self.local.n_total

    @property
    def n_flat(self) -> int:
        return self.n_shards * self.local.n_total

    @classmethod
    def for_tree(cls, tree, pspecs, mesh_shape, axes) -> "ShardFlatLayout":
        """Derive the layout from a SINGLE-REPLICA (shape-)tree.

        ``pspecs`` is the matching PartitionSpec tree (single-replica: no
        client dim), ``mesh_shape`` a mapping axis name -> size (``Mesh.shape``
        or a plain dict), ``axes`` the shard axes in flat-axis order.
        """
        axes = tuple(axes)
        sizes = tuple(int(mesh_shape[a]) for a in axes)
        spec_leaves = jax.tree.leaves(pspecs, is_leaf=lambda x: isinstance(x, P))
        paths_leaves = tree_paths(tree)
        if len(spec_leaves) != len(paths_leaves):
            raise ValueError(f"pspec tree has {len(spec_leaves)} leaves for "
                             f"{len(paths_leaves)} tree leaves")
        eff_specs, local_shapes, gshapes, split, uneven = [], [], [], [], []
        for (path, leaf), spec in zip(paths_leaves, spec_leaves):
            shape = tuple(leaf.shape)
            entries = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
            eff, loc, any_split, any_uneven = [], [], False, False
            for dim, entry in zip(shape, entries):
                shard_ax = _entry_axes(entry)
                alien = [a for a in shard_ax if a not in axes]
                if alien:
                    raise ValueError(
                        f"leaf {path!r}: spec {spec} uses axis {alien[0]!r} "
                        f"outside the shard axes {axes}")
                ext = 1
                for a in shard_ax:
                    ext *= int(mesh_shape[a])
                if ext > 1 and dim % ext == 0:
                    eff.append(entry)
                    loc.append(dim // ext)
                    any_split = True
                else:
                    # uneven extent (or size-1 axes): replicate this dim in
                    # every shard block — the GSPMD-equivalent fallback
                    if ext > 1:
                        any_uneven = True
                    eff.append(None)
                    loc.append(dim)
            eff_specs.append(P(*eff))
            local_shapes.append(tuple(loc))
            gshapes.append(shape)
            split.append(any_split)
            uneven.append(any_uneven)
        treedef = jax.tree.structure(tree)
        local_tree = jax.tree.unflatten(treedef, [
            jax.ShapeDtypeStruct(s, jnp.float32) for s in local_shapes])
        return cls(local=FlatLayout.for_tree(local_tree), axes=axes,
                   axis_sizes=sizes, specs=tuple(eff_specs),
                   global_shapes=tuple(gshapes), split=tuple(split),
                   uneven=tuple(uneven))

    # ---- shard_map specs ------------------------------------------------- #

    def flat_spec(self, lead=()) -> P:
        """Spec of the flat buffer: ``lead`` entries then the shard axes."""
        return P(*lead, self.axes)

    def leaf_specs(self, lead=()):
        """PartitionSpec tree for the (possibly batched) leaf tree."""
        return jax.tree.unflatten(
            self.local.treedef, [P(*lead, *tuple(s)) for s in self.specs])

    # ---- shard_map flatten / unflatten ----------------------------------- #

    def flatten(self, tree, mesh, lead=()):
        """Leaf tree (``len(lead)`` leading batch dims) -> sharded flat
        buffer ``(*batch, n_flat)`` — each device flattens only its local
        shards; no cross-device traffic."""
        bd = len(lead)
        f = jax.shard_map(lambda t: self.local.flatten(t, batch_dims=bd),
                          mesh=mesh, in_specs=(self.leaf_specs(lead),),
                          out_specs=self.flat_spec(lead), check_vma=False)
        return f(tree)

    def unflatten(self, buf, mesh, lead=()):
        """Sharded flat buffer -> the leaf tree, each device reconstructing
        its local shards (replicated-in-block leaves agree bit-for-bit across
        shards by construction: same elementwise math on identical inputs)."""
        bd = len(lead)
        f = jax.shard_map(lambda b: self.local.unflatten(b, batch_dims=bd),
                          mesh=mesh, in_specs=(self.flat_spec(lead),),
                          out_specs=self.leaf_specs(lead), check_vma=False)
        return f(buf)

    # ---- mesh-free reference (tests + differential oracle) ---------------- #

    def _shard_slices(self, s: int):
        """Per-leaf index tuples selecting shard ``s``'s local block."""
        coords = np.unravel_index(s, self.axis_sizes) if self.axes else ()
        by_axis = dict(zip(self.axes, (int(c) for c in coords)))
        size_of = dict(zip(self.axes, self.axis_sizes))
        out = []
        for spec, gshape, lshape in zip(self.specs, self.global_shapes,
                                        self.local.shapes):
            idx = []
            for dim, loc, entry in zip(
                    gshape, lshape,
                    tuple(spec) + (None,) * (len(gshape) - len(tuple(spec)))):
                ax = _entry_axes(entry)
                if not ax:
                    idx.append(slice(None))
                    continue
                k = 0
                for a in ax:           # major-first ravel over the entry axes
                    k = k * size_of[a] + by_axis[a]
                idx.append(slice(k * loc, (k + 1) * loc))
            out.append(tuple(idx))
        return out

    def flatten_ref(self, tree, batch_dims: int = 0):
        """Global-array reference of ``flatten`` (no mesh): shard-major
        concatenation of each shard's local flat block.  The shard_map path is
        pinned bitwise against this in tests/test_fused_sharded.py."""
        leaves = jax.tree.leaves(tree)
        pre = (slice(None),) * batch_dims
        blocks = []
        for s in range(self.n_shards):
            parts = [l[pre + sl].reshape(l.shape[:batch_dims] + (-1,))
                     .astype(jnp.float32)
                     for l, sl in zip(leaves, self._shard_slices(s))]
            blocks.append(jnp.concatenate(parts, axis=-1))
        return jnp.concatenate(blocks, axis=-1)

    def unflatten_ref(self, buf, batch_dims: int = 0):
        """Inverse of ``flatten_ref``: reassemble every leaf from its shard
        blocks (replicated-in-block leaves take any block's copy — they agree
        by contract)."""
        batch = buf.shape[:batch_dims]
        nl = self.n_local
        leaves = [jnp.zeros(batch + s, jnp.float32)
                  for s in self.global_shapes]
        pre = (slice(None),) * batch_dims
        for s in range(self.n_shards):
            block = buf[..., s * nl:(s + 1) * nl]
            for i, (sl, off, sz, lshape) in enumerate(zip(
                    self._shard_slices(s), self.local.offsets,
                    self.local.sizes, self.local.shapes)):
                part = block[..., off:off + sz].reshape(batch + lshape)
                leaves[i] = leaves[i].at[pre + sl].set(part)
        return jax.tree.unflatten(self.local.treedef, leaves)

    def describe(self) -> dict:
        """JSON-able summary for BuiltStep meta / dry-run artifacts."""
        return {
            "n_shards": self.n_shards,
            "axes": list(self.axes),
            "axis_sizes": list(self.axis_sizes),
            "n_local": self.n_local,
            "n_flat": self.n_flat,
            "leaves": [
                {"path": p, "global_shape": list(g), "local_shape": list(s),
                 "size": sz, "offset": o, "split": bool(sp),
                 "uneven_fallback": bool(un)}
                for p, g, s, sz, o, sp, un in zip(
                    self.local.paths, self.global_shapes, self.local.shapes,
                    self.local.sizes, self.local.offsets, self.split,
                    self.uneven)
            ],
        }


@dataclasses.dataclass(frozen=True)
class ShardedFlatPlan:
    """Everything the engine's fused fast path needs to run per model shard:
    the mesh, the shard-local layout, and the client-axes entry for the
    leading M dim (``None`` = client-replicated plans)."""
    mesh: Any
    layout: ShardFlatLayout
    client: Any = None

    @classmethod
    def build(cls, mesh, params_one, pspecs_one, axes,
              client=None) -> "ShardedFlatPlan":
        """``params_one``/``pspecs_one`` are single-replica (no client dim)."""
        layout = ShardFlatLayout.for_tree(params_one, pspecs_one,
                                          dict(mesh.shape), tuple(axes))
        return cls(mesh=mesh, layout=layout, client=client)
