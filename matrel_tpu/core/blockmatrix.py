"""BlockMatrix — the TPU-native distributed matrix representation (layer L2).

Reference semantics (SURVEY.md §2 "Block representation"): MatRel stores a
distributed matrix as a Spark Dataset/RDD of ``(rowBlkIdx, colBlkIdx,
MLMatrix)`` records with a fixed block size, partitioned across executors by a
RowPartitioner / ColumnPartitioner / BlockCyclicPartitioner.

TPU-native redesign: a BlockMatrix wraps ONE ``jax.Array`` laid out on a 2D
device mesh with a ``NamedSharding``. "Blocks" are the shards XLA already
manages; the partitioner choice collapses into the PartitionSpec. What
remains of the reference's representation is the metadata the optimizer
needs — logical shape, block size for cost granularity, and an nnz/sparsity
estimate (SURVEY.md §2 "Statistics / sparsity estimation").

Padding: logical dims are padded up to multiples of the mesh axis sizes so
every shard is equal-sized (XLA-friendly static shapes). The padded region is
zero; aggregate ops mask it where zeros would change the answer (max/min).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from matrel_tpu.config import MatrelConfig, default_config
from matrel_tpu.core import mesh as mesh_lib
from matrel_tpu.obs import trace as trace_lib

Array = jax.Array


def _pad_to(n: int, multiple: int) -> int:
    return int(math.ceil(n / multiple) * multiple) if multiple > 1 else n


@dataclasses.dataclass
class BlockMatrix:
    """A 2D-mesh-sharded distributed matrix.

    Attributes:
      data: the padded device array, shape ``padded_shape``.
      shape: the logical (unpadded) shape.
      mesh: the device mesh this matrix lives on.
      spec: PartitionSpec of ``data`` (how blocks map to devices).
      nnz: estimated number of structural nonzeros in the logical region,
        or None for "assume dense".
      block_size: logical tile edge for cost-model granularity.
      integral: every entry is an exact integer representable in f32 —
        the static fact the precision-tier planner's integer-exactness
        inference reads (ir/stats.infer_integral), so an "exact"
        accuracy SLA can route integer-shaped workloads (adjacency
        matrices, counts, boolean joins) onto the exact int32/int8 MXU
        paths. Auto-detected by from_numpy for integer/bool sources;
        declare it explicitly for integer-valued float data.
      int_abs_max: max|entry| of an integral matrix, recorded at
        construction (from_numpy computes it for integral sources) —
        the magnitude half of the exactness proof: the planner only
        auto-picks an int tier when the accumulated product provably
        fits the int32 accumulator (ir/stats.integral_abs_bound), so
        "exact" can never silently wrap. None = unproven (the chooser
        conservatively keeps f32).
    """

    data: Array
    shape: Tuple[int, int]
    mesh: Mesh
    spec: P
    nnz: Optional[int] = None
    block_size: int = 512
    integral: bool = False
    int_abs_max: Optional[float] = None

    # -- basic properties ---------------------------------------------------

    @property
    def padded_shape(self) -> Tuple[int, int]:
        return tuple(self.data.shape)  # type: ignore[return-value]

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def sparsity(self) -> float:
        """Fraction of nonzeros (density). 1.0 when unknown/dense."""
        if self.nnz is None:
            return 1.0
        n = self.shape[0] * self.shape[1]
        return self.nnz / n if n else 0.0

    @property
    def is_padded(self) -> bool:
        return self.padded_shape != self.shape

    def sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec)

    # -- construction -------------------------------------------------------

    @staticmethod
    def _padded_dims(shape: Tuple[int, int], mesh: Mesh) -> Tuple[int, int]:
        from matrel_tpu.core import padding
        return padding.padded_shape(tuple(shape), mesh)

    @classmethod
    def from_numpy(
        cls,
        arr: np.ndarray,
        mesh: Optional[Mesh] = None,
        spec: Optional[P] = None,
        dtype: Any = None,
        config: Optional[MatrelConfig] = None,
        nnz: Optional[int] = None,
        integral: Optional[bool] = None,
    ) -> "BlockMatrix":
        cfg = config or default_config()
        if integral is None:
            # integer/bool sources are integer-valued by construction;
            # float sources need the caller's word (checking every
            # entry would cost an O(n) host pass per construction)
            integral = bool(np.issubdtype(arr.dtype, np.integer)
                            or arr.dtype == np.bool_)
        # magnitude proof for the int-tier overflow gate — one O(n)
        # host max, noise next to the device_put copy, only for the
        # (rare) integral sources that can use it
        int_abs_max = (float(np.abs(arr).max()) if integral and arr.size
                       else (0.0 if integral else None))
        if arr.ndim == 1:
            arr = arr.reshape(-1, 1)
        if arr.ndim != 2:
            raise ValueError(f"BlockMatrix is 2D; got shape {arr.shape}")
        mesh = mesh or mesh_lib.make_mesh(cfg.mesh_shape, cfg.mesh_axis_names)
        dtype = dtype or cfg.default_dtype
        shape = tuple(arr.shape)
        ps = cls._padded_dims(shape, mesh)
        if spec is None:
            from matrel_tpu.core import padding
            spec = padding.canonical_spec(ps, mesh)
        if ps != shape:
            padded = np.zeros(ps, dtype=dtype)
            padded[: shape[0], : shape[1]] = arr
        else:
            padded = np.asarray(arr, dtype=dtype)
        data = jax.device_put(padded, NamedSharding(mesh, spec))
        return cls(data=data, shape=shape, mesh=mesh, spec=spec, nnz=nnz,
                   block_size=cfg.block_size, integral=bool(integral),
                   int_abs_max=int_abs_max)

    @classmethod
    def from_array(
        cls,
        data: Array,
        shape: Tuple[int, int],
        mesh: Mesh,
        spec: P,
        nnz: Optional[int] = None,
        block_size: Optional[int] = None,
    ) -> "BlockMatrix":
        return cls(data=data, shape=tuple(shape), mesh=mesh, spec=spec,
                   nnz=nnz, block_size=block_size or default_config().block_size)

    @classmethod
    def random(
        cls,
        shape: Tuple[int, int],
        mesh: Optional[Mesh] = None,
        spec: Optional[P] = None,
        dtype: Any = None,
        seed: int = 0,
        config: Optional[MatrelConfig] = None,
    ) -> "BlockMatrix":
        """Uniform [0,1) random matrix, generated device-side (no host copy)."""
        cfg = config or default_config()
        mesh = mesh or mesh_lib.make_mesh(cfg.mesh_shape, cfg.mesh_axis_names)
        dtype = dtype or cfg.default_dtype
        ps = cls._padded_dims(tuple(shape), mesh)
        if spec is None:
            from matrel_tpu.core import padding
            spec = padding.canonical_spec(ps, mesh)
        sharding = NamedSharding(mesh, spec)

        @jax.jit  # matlint: disable=ML010 construction-time helper — arrays are born here, before any plan exists
        def _gen():
            vals = jax.random.uniform(jax.random.PRNGKey(seed), ps, dtype=jnp.float32)
            r = jnp.arange(ps[0])[:, None] < shape[0]
            c = jnp.arange(ps[1])[None, :] < shape[1]
            vals = jnp.where(r & c, vals, 0.0).astype(dtype)
            return jax.lax.with_sharding_constraint(vals, sharding)

        return cls(data=_gen(), shape=tuple(shape), mesh=mesh, spec=spec,
                   nnz=None, block_size=cfg.block_size)

    @classmethod
    def zeros(cls, shape, mesh=None, spec=None, dtype=None, config=None) -> "BlockMatrix":
        cfg = config or default_config()
        mesh = mesh or mesh_lib.make_mesh(cfg.mesh_shape, cfg.mesh_axis_names)
        dtype = dtype or cfg.default_dtype
        ps = cls._padded_dims(tuple(shape), mesh)
        if spec is None:
            from matrel_tpu.core import padding
            spec = padding.canonical_spec(ps, mesh)
        sharding = NamedSharding(mesh, spec)
        data = jax.jit(lambda: jax.lax.with_sharding_constraint(  # matlint: disable=ML010 construction-time helper — arrays are born here, before any plan exists
            jnp.zeros(ps, dtype=dtype), sharding))()
        return cls(data=data, shape=tuple(shape), mesh=mesh, spec=spec, nnz=0,
                   block_size=cfg.block_size)

    @classmethod
    def eye(cls, n: int, mesh=None, spec=None, dtype=None, config=None) -> "BlockMatrix":
        cfg = config or default_config()
        mesh = mesh or mesh_lib.make_mesh(cfg.mesh_shape, cfg.mesh_axis_names)
        dtype = dtype or cfg.default_dtype
        ps = cls._padded_dims((n, n), mesh)
        if spec is None:
            from matrel_tpu.core import padding
            spec = padding.canonical_spec(ps, mesh)
        sharding = NamedSharding(mesh, spec)

        @jax.jit  # matlint: disable=ML010 construction-time helper — arrays are born here, before any plan exists
        def _gen():
            r = jnp.arange(ps[0])[:, None]
            c = jnp.arange(ps[1])[None, :]
            vals = jnp.where((r == c) & (r < n), 1.0, 0.0).astype(dtype)
            return jax.lax.with_sharding_constraint(vals, sharding)

        return cls(data=_gen(), shape=(n, n), mesh=mesh, spec=spec, nnz=n,
                   block_size=cfg.block_size)

    @classmethod
    def from_block_fn(
        cls,
        shape: Tuple[int, int],
        fn: Callable[[Array, Array], Array],
        mesh=None,
        spec=None,
        dtype=None,
        config=None,
        nnz: Optional[int] = None,
    ) -> "BlockMatrix":
        """Generate entries from ``fn(row_idx, col_idx)`` device-side.

        The analogue of the reference's per-block generator constructors:
        fn receives broadcastable index grids and returns values.
        """
        cfg = config or default_config()
        mesh = mesh or mesh_lib.make_mesh(cfg.mesh_shape, cfg.mesh_axis_names)
        dtype = dtype or cfg.default_dtype
        ps = cls._padded_dims(tuple(shape), mesh)
        if spec is None:
            from matrel_tpu.core import padding
            spec = padding.canonical_spec(ps, mesh)
        sharding = NamedSharding(mesh, spec)

        @jax.jit  # matlint: disable=ML010 construction-time helper — arrays are born here, before any plan exists
        def _gen():
            r = jnp.arange(ps[0])[:, None]
            c = jnp.arange(ps[1])[None, :]
            vals = fn(r, c).astype(dtype)
            vals = jnp.where((r < shape[0]) & (c < shape[1]), vals, 0)
            return jax.lax.with_sharding_constraint(vals, sharding)

        return cls(data=_gen(), shape=tuple(shape), mesh=mesh, spec=spec,
                   nnz=nnz, block_size=cfg.block_size)

    # -- materialisation ----------------------------------------------------

    def to_numpy(self) -> np.ndarray:
        """Gather to host, dropping padding."""
        with trace_lib.entry("fetch") as sp:
            if sp.live:
                # the record's split: was the answer there (a flag read);
                # the wait for it, with the copy issued first as
                # device_get issues it, so that it follows the answer
                # with no Python between, as when dark; what the copy
                # takes after the answer
                sp.set(ready=self.data.is_ready())
                with trace_lib.span("fetch.wait"):
                    self.data.copy_to_host_async()
                    self.data.block_until_ready()
                with trace_lib.span("fetch.copy"):
                    full = np.asarray(jax.device_get(self.data))
            else:
                full = np.asarray(jax.device_get(self.data))
            sp.set(bytes=full.nbytes)
            return full[: self.shape[0], : self.shape[1]]

    def block_until_ready(self) -> "BlockMatrix":
        self.data.block_until_ready()
        return self

    # -- sharding management ------------------------------------------------

    def with_spec(self, spec: P) -> "BlockMatrix":
        """Reshard (the analogue of repartitioning by a different partitioner)."""
        if spec == self.spec:
            return self
        data = jax.device_put(self.data, NamedSharding(self.mesh, spec))
        return dataclasses.replace(self, data=data, spec=spec)

    def valid_mask(self) -> Array:
        """Boolean mask of the logical (non-padding) region, padded shape."""
        ps = self.padded_shape
        r = jnp.arange(ps[0])[:, None] < self.shape[0]
        c = jnp.arange(ps[1])[None, :] < self.shape[1]
        return r & c

    # -- lazy DSL (builds IR; mirrors the reference's Dataset implicits) ----
    # SURVEY.md §2 "Scala DSL": t(), multiply(), add(), elemMultiply(),
    # divide(), power(), rowSum(), colSum(), sum(), trace(), vec(),
    # rankOneUpdate(), selection/join methods. Each returns a lazy MatExpr.

    def expr(self):
        from matrel_tpu.ir.expr import leaf
        return leaf(self)

    def t(self):
        return self.expr().t()

    def multiply(self, other):
        return self.expr().multiply(other)

    def matmul(self, other):
        return self.expr().multiply(other)

    def add(self, other):
        return self.expr().add(other)

    def subtract(self, other):
        return self.expr().subtract(other)

    def elem_multiply(self, other):
        return self.expr().elem_multiply(other)

    def divide(self, other):
        return self.expr().divide(other)

    def add_scalar(self, s):
        return self.expr().add_scalar(s)

    def multiply_scalar(self, s):
        return self.expr().multiply_scalar(s)

    def power(self, p):
        return self.expr().power(p)

    def row_sum(self):
        return self.expr().row_sum()

    def col_sum(self):
        return self.expr().col_sum()

    def sum(self):
        return self.expr().sum()

    def trace(self):
        return self.expr().trace()

    def norm(self, kind: str = "fro"):
        return self.expr().norm(kind)

    def inverse(self):
        return self.expr().inverse()

    def solve(self, b, assume: str = "general"):
        return self.expr().solve(b, assume=assume)

    def vec(self):
        return self.expr().vec()

    def rank_one_update(self, u, v):
        return self.expr().rank_one_update(u, v)

    def select_value(self, predicate, **kw):
        return self.expr().select_value(predicate, **kw)

    def select_index(self, *, rows=None, cols=None):
        return self.expr().select_index(rows=rows, cols=cols)

    def join_on_index(self, other, merge):
        return self.expr().join_on_index(other, merge)

    def __matmul__(self, other):
        return self.multiply(other)

    def __add__(self, other):
        return self.add(other)

    def __sub__(self, other):
        return self.subtract(other)

    def __mul__(self, other):
        if isinstance(other, (int, float)):
            return self.multiply_scalar(other)
        return self.elem_multiply(other)

    def __repr__(self) -> str:
        return (f"BlockMatrix(shape={self.shape}, dtype={self.dtype}, "
                f"spec={self.spec}, nnz={self.nnz}, "
                f"mesh={dict(self.mesh.shape)})")


jax.tree_util.register_pytree_node(
    BlockMatrix,
    lambda bm: ((bm.data,), (bm.shape, bm.mesh, bm.spec, bm.nnz, bm.block_size)),
    lambda aux, children: BlockMatrix(children[0], *aux),
)
