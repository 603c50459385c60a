"""DistributedLayout & TensorSpec: the paper's LayoutMapping promoted to a 512-chip mesh.

The central observation (DESIGN.md §3): GSPMD sharding *is* a layout mapping — a
strided-block map from the logical multi-index domain onto
(device-grid coordinates) × (local offsets). We make it a first-class
``LayoutMapping`` subclass so the paper's Table I property algebra (uniqueness,
stridedness, contiguity *per shard*) applies verbatim, and derive JAX
``NamedSharding``s from it. One mechanism then expresses DP / FSDP / TP / EP / SP.

``TensorSpec`` is the framework's universal tensor descriptor — the mdspan "type":

    TensorSpec(extents, logical_axes, dtype, accessor, init)

Every parameter, activation boundary, optimizer slot and cache in the model zoo is
declared as a TensorSpec; shardings, dry-run ShapeDtypeStructs, initializers and
quantized-kernel dispatch all derive from it. Logical axis names are bound to mesh
axes by a ``ShardingRules`` table (per architecture × per shape), so re-targeting
parallelism = swapping a rules table, never touching model code — the paper's
"change the layout in the type of A without changing the algorithm" at cluster scale.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .accessors import Accessor, BasicAccessor, QuantizedAccessor
from .extents import Extents
from .layouts import LayoutMapping, LayoutRight, _row_major_strides

AxisBinding = Union[None, str, Tuple[str, ...]]


# ---------------------------------------------------------------------------------
# DistributedLayout: a real LayoutMapping over (devices × local memory)
# ---------------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class DistributedLayout(LayoutMapping):
    """Block map: logical index -> (device coordinate per sharded dim, local offset).

    ``mesh_axes[r]`` gives the mesh-axis name(s) dim r is sharded over (None =
    replicated in that dim). ``axis_sizes`` maps axis name -> size. The codomain is
    linearized as device_id * local_span + local_offset, making this a genuine
    single-offset LayoutMapping whose Table I properties are testable:

      is_unique()      True  (block sharding never aliases)
      is_contiguous()  True iff every sharded dim divides evenly AND sharded dims
                       are a prefix of the dim order (device blocks tile the domain)
      is_strided()     True per-shard; globally only when one dim is sharded and it
                       is the outermost — matches GSPMD reality.
    """

    extents: Extents
    mesh_axes: Tuple[AxisBinding, ...]
    axis_sizes: Dict[str, int]

    def __post_init__(self):
        if len(self.mesh_axes) != self.extents.rank:
            raise TypeError("mesh_axes rank mismatch")

    # -- geometry -----------------------------------------------------------------
    def dim_shards(self, r: int) -> int:
        b = self.mesh_axes[r]
        if b is None:
            return 1
        names = (b,) if isinstance(b, str) else b
        n = 1
        for nm in names:
            n *= self.axis_sizes[nm]
        return n

    def local_shape(self) -> Tuple[int, ...]:
        return tuple(
            -(-self.extents.extent(r) // self.dim_shards(r))
            for r in range(self.extents.rank)
        )

    def num_devices_used(self) -> int:
        n = 1
        for r in range(self.extents.rank):
            n *= self.dim_shards(r)
        return n

    def local_span(self) -> int:
        n = 1
        for s in self.local_shape():
            n *= s
        return n

    # -- LayoutMapping ------------------------------------------------------------
    def __call__(self, *idx):
        local = self.local_shape()
        lstr = _row_major_strides(local)
        shard_counts = [self.dim_shards(r) for r in range(self.extents.rank)]
        dstr = _row_major_strides(tuple(shard_counts))
        dev = 0
        loc = 0
        for r, i in enumerate(idx):
            dev = dev + (i // local[r]) * dstr[r]
            loc = loc + (i % local[r]) * lstr[r]
        return dev * self.local_span() + loc

    def device_of(self, *idx):
        local = self.local_shape()
        shard_counts = tuple(self.dim_shards(r) for r in range(self.extents.rank))
        dstr = _row_major_strides(shard_counts)
        dev = 0
        for r, i in enumerate(idx):
            dev = dev + (i // local[r]) * dstr[r]
        return dev

    def local_offset(self, *idx):
        local = self.local_shape()
        lstr = _row_major_strides(local)
        loc = 0
        for r, i in enumerate(idx):
            loc = loc + (i % local[r]) * lstr[r]
        return loc

    def required_span_size(self) -> int:
        return self.num_devices_used() * self.local_span()

    def is_unique(self) -> bool:
        return True

    @classmethod
    def is_always_unique(cls) -> bool:
        return True

    def is_contiguous(self) -> bool:
        # no padding and device-major order coincides with row-major nesting
        for r in range(self.extents.rank):
            if self.extents.extent(r) % self.dim_shards(r) != 0:
                return False
        sharded = [r for r in range(self.extents.rank) if self.dim_shards(r) > 1]
        return sharded == list(range(len(sharded)))

    def is_strided(self) -> bool:
        sharded = [r for r in range(self.extents.rank) if self.dim_shards(r) > 1]
        return len(sharded) == 0 or (sharded == [0] and self.extents.extent(0) % self.dim_shards(0) == 0)

    def stride(self, r: int) -> int:
        if not self.is_strided():
            from .layouts import LayoutError

            raise LayoutError("DistributedLayout is not globally strided here")
        # Strided only when the single sharded dim is outermost and divides evenly;
        # then the boundary hop equals the within-shard step (= the local stride):
        #   local_span - (local[r]-1)*lstr[r] == lstr[r]  for row-major local layouts.
        # (Found by the hypothesis Table-I law tests — see tests/test_layouts.py.)
        local = self.local_shape()
        lstr = _row_major_strides(local)
        return lstr[r]

    # -- JAX binding ----------------------------------------------------------------
    def pspec(self) -> PartitionSpec:
        return PartitionSpec(*self.mesh_axes)

    def sharding(self, mesh: Mesh) -> NamedSharding:
        return NamedSharding(mesh, self.pspec())


# ---------------------------------------------------------------------------------
# ShardingRules: logical axis name -> mesh axis binding
# ---------------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Maps logical axis names to mesh axes; the per-(arch × shape) layout policy.

    ``rules["embed"] = "model"`` etc. Unknown names are replicated. A dim is only
    sharded if its size divides the product of the bound mesh axes — otherwise the
    binding is dropped for that tensor (e.g. kv_heads=8 with a 16-way model axis →
    replicated KV, the Megatron fallback), keeping every spec lowerable.
    """

    rules: Dict[str, AxisBinding]
    strict_divisibility: bool = True

    def binding_for(
        self, logical_axes: Sequence[Optional[str]], shape: Sequence[int], mesh: Mesh
    ) -> Tuple[AxisBinding, ...]:
        used: set = set()
        out = []
        for name, size in zip(logical_axes, shape):
            b = self.rules.get(name) if name is not None else None
            if b is None:
                out.append(None)
                continue
            names = (b,) if isinstance(b, str) else tuple(b)
            # drop axes already consumed by an earlier dim of this tensor
            names = tuple(n for n in names if n not in used and n in mesh.shape)
            if not names:
                out.append(None)
                continue
            nshards = math.prod(mesh.shape[n] for n in names)
            if self.strict_divisibility and size % nshards != 0:
                out.append(None)  # divisibility fallback (replicate)
                continue
            used.update(names)
            out.append(names[0] if len(names) == 1 else names)
        return tuple(out)

    def pspec(self, logical_axes, shape, mesh) -> PartitionSpec:
        return PartitionSpec(*self.binding_for(logical_axes, shape, mesh))

    def sharding(self, logical_axes, shape, mesh) -> NamedSharding:
        return NamedSharding(mesh, self.pspec(logical_axes, shape, mesh))


# ---------------------------------------------------------------------------------
# TensorSpec: the universal mdspan-style descriptor
# ---------------------------------------------------------------------------------
InitFn = Callable[[jax.Array, Tuple[int, ...], Any], jax.Array]


def _init_zeros(key, shape, dtype):
    return jnp.zeros(shape, dtype)


def _init_ones(key, shape, dtype):
    return jnp.ones(shape, dtype)


def _init_normal(stddev: float) -> InitFn:
    def f(key, shape, dtype):
        return (jax.random.normal(key, shape, jnp.float32) * stddev).astype(dtype)

    return f


def _init_fan_in(in_axes: Tuple[int, ...]) -> InitFn:
    """Normal with std 1/sqrt(fan_in), fan_in the product of the contracted
    axes ``in_axes``. They count from the end, so the leading layer axis of a
    stacked spec is never counted."""

    def f(key, shape, dtype):
        fan_in = math.prod(shape[a] for a in in_axes) if len(shape) >= 2 else shape[-1]
        std = 1.0 / math.sqrt(max(fan_in, 1))
        return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)

    return f


INITS: Dict[str, Any] = {
    "zeros": _init_zeros,
    "ones": _init_ones,
    # (…, d_in, d_out) matrices
    "fan_in": _init_fan_in((-2,)),
    # head-factored attention weights: (…, d, heads, head_dim) projections
    # into the heads contract d; (…, heads, head_dim, d) out of them contract
    # heads × head_dim
    "fan_in_to_heads": _init_fan_in((-3,)),
    "fan_in_from_heads": _init_fan_in((-3, -2)),
    "embed": _init_normal(0.02),
    "normal": _init_normal(0.02),
}


@dataclasses.dataclass(frozen=True)
class TensorSpec:
    """extents × logical axes × dtype × accessor: a distributed mdspan descriptor."""

    shape: Tuple[int, ...]
    logical_axes: Tuple[Optional[str], ...]
    dtype: Any = jnp.bfloat16
    init: str = "fan_in"
    static: Tuple[bool, ...] = ()  # per-dim compile-time-specializable flag
    accessor: Optional[Accessor] = None  # None -> BasicAccessor(dtype)

    def __post_init__(self):
        if len(self.logical_axes) != len(self.shape):
            raise TypeError(f"axes/shape rank mismatch: {self}")

    # -- mdspan views -------------------------------------------------------------
    def extents(self) -> Extents:
        static = self.static if self.static else tuple(True for _ in self.shape)
        return Extents(
            tuple(s if st else None for s, st in zip(self.shape, static)), tuple(self.shape)
        )

    def the_accessor(self) -> Accessor:
        return self.accessor if self.accessor is not None else BasicAccessor(self.dtype)

    def distributed_layout(self, mesh: Mesh, rules: ShardingRules) -> DistributedLayout:
        binding = rules.binding_for(self.logical_axes, self.shape, mesh)
        return DistributedLayout(self.extents(), binding, dict(mesh.shape))

    # -- JAX binding ----------------------------------------------------------------
    def sharding(self, mesh: Mesh, rules: ShardingRules) -> NamedSharding:
        return rules.sharding(self.logical_axes, self.shape, mesh)

    def shape_struct(self, mesh: Optional[Mesh] = None, rules: Optional[ShardingRules] = None):
        if self.is_quantized():
            acc = self.the_accessor()
            tree = self._quantized_struct_tree()
            if mesh is not None:
                shard = self.sharding(mesh, rules)
                tree = {
                    k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=self._q_sharding(k, mesh, rules))
                    for k, v in tree.items()
                }
            return tree
        if mesh is None:
            return jax.ShapeDtypeStruct(self.shape, self.dtype)
        return jax.ShapeDtypeStruct(self.shape, self.dtype, sharding=self.sharding(mesh, rules))

    # -- quantized storage ----------------------------------------------------------
    def is_quantized(self) -> bool:
        return isinstance(self.accessor, QuantizedAccessor)

    def _q_shapes(self):
        acc = self.accessor
        *lead, last = self.shape
        if last % acc.block != 0:
            raise ValueError(f"quantized last dim {last} must divide block {acc.block}")
        qlast = last if acc.bits == 8 else last // 2
        return tuple(lead) + (qlast,), tuple(lead) + (last // acc.block,)

    def _quantized_struct_tree(self):
        qs, ss = self._q_shapes()
        return {
            "q": jax.ShapeDtypeStruct(qs, jnp.int8),
            "scale": jax.ShapeDtypeStruct(ss, jnp.float32),
        }

    def _q_sharding(self, part: str, mesh, rules):
        # scales inherit the q sharding on all but the (blocked) last dim
        binding = rules.binding_for(self.logical_axes, self.shape, mesh)
        if part == "scale":
            *lead, last = binding
            qshape, sshape = self._q_shapes()
            nblocks = sshape[-1]
            if last is not None:
                names = (last,) if isinstance(last, str) else last
                n = math.prod(mesh.shape[x] for x in names)
                if nblocks % n != 0:
                    last = None
            binding = tuple(lead) + (last,)
        return NamedSharding(mesh, PartitionSpec(*binding))

    # -- init ------------------------------------------------------------------------
    def initialize(self, key: jax.Array):
        init = INITS[self.init]
        dense = init(key, self.shape, jnp.float32 if self.is_quantized() else self.dtype)
        if self.is_quantized():
            return quantize_array(dense, self.accessor)
        return dense

    def mdspan_over(self, buffers) -> "Any":
        from .mdspan import MdSpan

        return MdSpan(buffers, LayoutRight(self.extents()), self.the_accessor())


def quantize_array(dense: jax.Array, acc: QuantizedAccessor):
    """Quantize along the LAST dim in blocks of ``acc.block``; returns {"q","scale"}.

    The N-D batched form of ``QuantizedAccessor.from_codomain`` (same math,
    vectorized over leading dims) — used for weights and optimizer state.
    """
    *lead, last = dense.shape
    if last % acc.block != 0:
        raise ValueError(f"last dim {last} % block {acc.block} != 0")
    nb = last // acc.block
    x = jnp.asarray(dense, jnp.float32).reshape(*lead, nb, acc.block)
    absmax = jnp.max(jnp.abs(x), axis=-1)
    scale = jnp.where(absmax > 0, absmax / acc.qmax, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(x / scale[..., None]), -acc.qmax, acc.qmax).astype(jnp.int8)
    q = q.reshape(*lead, last)
    if acc.bits == 4:
        q2 = q.reshape(*lead, last // 2, 2)
        q = ((q2[..., 0] & 0x0F) | ((q2[..., 1] & 0x0F) << 4)).astype(jnp.int8)
    return {"q": q, "scale": scale}


def dequantize_array(bufs, acc: QuantizedAccessor) -> jax.Array:
    q = bufs["q"]
    scale = bufs["scale"]
    if acc.bits == 4:
        lo = (q & 0x0F).astype(jnp.int8)
        hi = ((q >> 4) & 0x0F).astype(jnp.int8)
        lo = jnp.where(lo >= 8, lo - 16, lo)
        hi = jnp.where(hi >= 8, hi - 16, hi)
        q = jnp.stack([lo, hi], axis=-1).reshape(*q.shape[:-1], q.shape[-1] * 2)
    *lead, last = q.shape
    nb = scale.shape[-1]
    x = q.astype(jnp.float32).reshape(*lead, nb, last // nb) * scale[..., None]
    return x.reshape(*lead, last).astype(acc.element_type)


# ---------------------------------------------------------------------------------
# pytree-of-spec helpers
# ---------------------------------------------------------------------------------
def is_spec(x) -> bool:
    return isinstance(x, TensorSpec)


def tree_shardings(specs, mesh: Mesh, rules: ShardingRules):
    return jax.tree.map(
        lambda s: (
            {k: s._q_sharding(k, mesh, rules) for k in ("q", "scale")}
            if s.is_quantized()
            else s.sharding(mesh, rules)
        ),
        specs,
        is_leaf=is_spec,
    )


def tree_shape_structs(specs, mesh: Optional[Mesh] = None, rules: Optional[ShardingRules] = None):
    return jax.tree.map(lambda s: s.shape_struct(mesh, rules), specs, is_leaf=is_spec)


def tree_initialize(specs, key: jax.Array):
    leaves, treedef = jax.tree.flatten(specs, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [s.initialize(k) for s, k in zip(leaves, keys)])


def tree_param_bytes(specs) -> int:
    total = 0
    for s in jax.tree.leaves(specs, is_leaf=is_spec):
        if s.is_quantized():
            qs, ss = s._q_shapes()
            total += math.prod(qs) + math.prod(ss) * 4
        else:
            total += math.prod(s.shape) * jnp.dtype(s.dtype).itemsize
    return total


def tree_param_count(specs) -> int:
    return sum(math.prod(s.shape) for s in jax.tree.leaves(specs, is_leaf=is_spec))
