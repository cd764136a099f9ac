"""BlockSparseMatrix — block-granular sparse matrices (SURVEY.md §7.7).

Reference semantics: MatRel stores sparse blocks as MLlib CSC matrices
inside the same (rowBlk, colBlk, matrix) records, and its cost model is
sparsity-aware (SURVEY.md §2 "Local matrix kernels", "Statistics").

TPU-native redesign: element-granular CSC is hostile to the MXU; the
idiomatic unit is the BLOCK. A BlockSparseMatrix keeps only nonzero
``block_size × block_size`` tiles, as a dense stack:

    blocks:     f32/bf16 [nnzb, bs, bs]   — the tile payloads
    block_rows: int32 [nnzb]              — tile row index  (sorted)
    block_cols: int32 [nnzb]              — tile col index

SpMM against a dense BlockMatrix runs as gather → batched MXU matmul →
segment-sum (ops/spmm.py), or the Pallas scalar-prefetch kernel
(ops/pallas_spmm.py) on TPU. Element-level sparsity inside a kept tile is
simply stored as zeros — the MXU multiplies them at full speed, which beats
any gather-based element skipping until density drops far below what the
reference's workloads use (1%, clustered).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from matrel_tpu.config import MatrelConfig, default_config
from matrel_tpu.core import mesh as mesh_lib
from matrel_tpu.obs import trace as trace_lib

Array = jax.Array


@dataclasses.dataclass
class BlockSparseMatrix:
    """Block-sparse matrix with dense tile payloads.

    Tiles are replicated across the mesh (the broadcast operand of a
    BMM-style SpMM); the dense operand carries the sharding.
    """

    blocks: Array        # [nnzb, bs, bs]
    block_rows: Array    # [nnzb] int32, sorted (row-major order)
    block_cols: Array    # [nnzb] int32
    shape: Tuple[int, int]
    block_size: int
    mesh: Mesh

    @property
    def nnzb(self) -> int:
        return self.blocks.shape[0]

    @property
    def grid(self) -> Tuple[int, int]:
        bs = self.block_size
        return (math.ceil(self.shape[0] / bs), math.ceil(self.shape[1] / bs))

    @property
    def nnz(self) -> int:
        """Upper-bound structural nnz (block granular)."""
        return self.nnzb * self.block_size * self.block_size

    @property
    def density(self) -> float:
        gr, gc = self.grid
        return self.nnzb / (gr * gc) if gr * gc else 0.0

    @property
    def dtype(self):
        return self.blocks.dtype

    # -- construction -------------------------------------------------------

    @classmethod
    def from_numpy(cls, arr: np.ndarray, block_size: Optional[int] = None,
                   mesh: Optional[Mesh] = None,
                   config: Optional[MatrelConfig] = None,
                   dtype: Any = None) -> "BlockSparseMatrix":
        """Keep only tiles containing at least one nonzero."""
        cfg = config or default_config()
        bs = block_size or cfg.block_size
        mesh = mesh or mesh_lib.make_mesh(cfg.mesh_shape, cfg.mesh_axis_names)
        dtype = dtype or cfg.default_dtype
        n, m = arr.shape
        gr, gc = math.ceil(n / bs), math.ceil(m / bs)
        padded = np.zeros((gr * bs, gc * bs), dtype=dtype)
        padded[:n, :m] = arr
        tiles = padded.reshape(gr, bs, gc, bs).transpose(0, 2, 1, 3)
        nz = np.argwhere(np.abs(tiles).sum(axis=(2, 3)) > 0)
        if len(nz) == 0:
            nz = np.zeros((1, 2), dtype=np.int64)  # keep one zero tile
        order = np.lexsort((nz[:, 1], nz[:, 0]))   # row-major sort
        nz = nz[order]
        payload = tiles[nz[:, 0], nz[:, 1]]
        rep = NamedSharding(mesh, P())
        return cls(
            blocks=jax.device_put(payload.astype(dtype), rep),
            block_rows=jax.device_put(nz[:, 0].astype(np.int32), rep),
            block_cols=jax.device_put(nz[:, 1].astype(np.int32), rep),
            shape=(n, m), block_size=bs, mesh=mesh,
        )

    @classmethod
    def from_scipy(cls, sp, block_size: Optional[int] = None,
                   mesh: Optional[Mesh] = None,
                   config: Optional[MatrelConfig] = None,
                   dtype: Any = None) -> "BlockSparseMatrix":
        """From a scipy.sparse matrix (the CSC-block ingestion path of the
        reference, SURVEY.md §2 'Local matrix kernels'): element-sparse
        input is bucketed into block-granular payloads WITHOUT densifying
        the full matrix — only touched tiles are materialised."""
        coo = sp.tocoo()
        return cls.from_coo_arrays(coo.row, coo.col, coo.data, coo.shape,
                                   block_size=block_size, mesh=mesh,
                                   config=config, dtype=dtype)

    @classmethod
    def from_coo_arrays(cls, rows, cols, vals, shape: Tuple[int, int],
                        block_size: Optional[int] = None,
                        mesh: Optional[Mesh] = None,
                        config: Optional[MatrelConfig] = None,
                        dtype: Any = None) -> "BlockSparseMatrix":
        """From raw COO coordinate arrays — the shared bucketing core of
        ``from_scipy`` and the executor's COOMatrix→block-sparse
        conversion for the SpGEMM dispatch (ops/spgemm.py): only touched
        tiles are materialised, the full matrix never is. Duplicate
        coordinates accumulate (scipy COO semantics)."""
        cfg = config or default_config()
        bs = block_size or cfg.block_size
        mesh = mesh or mesh_lib.make_mesh(cfg.mesh_shape, cfg.mesh_axis_names)
        dtype = dtype or cfg.default_dtype
        rows = np.asarray(rows, np.int64).ravel()
        cols = np.asarray(cols, np.int64).ravel()
        vals = np.asarray(vals).ravel()
        n, m = shape
        gc = math.ceil(m / bs)
        bi = rows // bs
        bj = cols // bs
        keys = bi * gc + bj
        uniq, tile_idx = np.unique(keys, return_inverse=True)
        payload = np.zeros((max(len(uniq), 1), bs, bs), dtype=dtype)
        np.add.at(payload,
                  (tile_idx.ravel(), rows % bs, cols % bs),
                  vals.astype(payload.dtype))
        trows = (uniq // gc).astype(np.int32)
        tcols = (uniq % gc).astype(np.int32)
        if len(uniq) == 0:
            trows = np.zeros(1, np.int32)
            tcols = np.zeros(1, np.int32)
        rep = NamedSharding(mesh, P())
        return cls(blocks=jax.device_put(payload, rep),
                   block_rows=jax.device_put(trows, rep),
                   block_cols=jax.device_put(tcols, rep),
                   shape=(int(n), int(m)), block_size=bs, mesh=mesh)

    @classmethod
    def random(cls, shape: Tuple[int, int], block_density: float,
               block_size: Optional[int] = None, mesh: Optional[Mesh] = None,
               seed: int = 0, config: Optional[MatrelConfig] = None,
               dtype: Any = None) -> "BlockSparseMatrix":
        """Random block-sparse matrix: a uniform sample of nonzero tiles
        filled with uniform values — the BASELINE row-4 generator, built
        device-side per tile (host only materialises indices)."""
        cfg = config or default_config()
        bs = block_size or cfg.block_size
        mesh = mesh or mesh_lib.make_mesh(cfg.mesh_shape, cfg.mesh_axis_names)
        dtype = dtype or cfg.default_dtype
        n, m = shape
        gr, gc = math.ceil(n / bs), math.ceil(m / bs)
        rng = np.random.default_rng(seed)
        total = gr * gc
        nnzb = max(1, int(round(total * block_density)))
        flat = rng.choice(total, size=nnzb, replace=False)
        flat.sort()
        rows, cols = (flat // gc).astype(np.int32), (flat % gc).astype(np.int32)
        rep = NamedSharding(mesh, P())

        @jax.jit  # matlint: disable=ML010 construction-time helper — arrays are born here, before any plan exists
        def gen():
            vals = jax.random.uniform(
                jax.random.PRNGKey(seed), (nnzb, bs, bs), dtype=jnp.float32)
            return jax.lax.with_sharding_constraint(vals.astype(dtype), rep)

        return cls(blocks=gen(),
                   block_rows=jax.device_put(rows, rep),
                   block_cols=jax.device_put(cols, rep),
                   shape=shape, block_size=bs, mesh=mesh)

    # -- materialisation ----------------------------------------------------

    def to_numpy(self) -> np.ndarray:
        gr, gc = self.grid
        bs = self.block_size
        with trace_lib.entry("fetch") as sp:
            out = np.zeros((gr * bs, gc * bs), dtype=self.blocks.dtype)
            br = np.asarray(self.block_rows)
            bc = np.asarray(self.block_cols)
            blocks = np.asarray(self.blocks)
            sp.set(bytes=blocks.nbytes + br.nbytes + bc.nbytes)
            for i in range(self.nnzb):
                out[br[i] * bs:(br[i] + 1) * bs, bc[i] * bs:(bc[i] + 1) * bs] = blocks[i]
            return out[: self.shape[0], : self.shape[1]]

    def to_dense(self, config: Optional[MatrelConfig] = None):
        """Scatter tiles into a dense BlockMatrix (device-side)."""
        from matrel_tpu.core.blockmatrix import BlockMatrix
        from matrel_tpu.core import padding
        cfg = config or default_config()
        gr, gc = self.grid
        bs = self.block_size
        pshape = padding.padded_shape(self.shape, self.mesh)
        sharding = padding.canonical_sharding(pshape, self.mesh)

        @jax.jit  # matlint: disable=ML010 construction-time helper — arrays are born here, before any plan exists
        def scatter(blocks, br, bc):
            full = jnp.zeros((gr, gc, bs, bs), dtype=blocks.dtype)
            full = full.at[br, bc].set(blocks)
            dense = full.transpose(0, 2, 1, 3).reshape(gr * bs, gc * bs)
            dense = dense[: pshape[0], : pshape[1]]
            if dense.shape != pshape:
                dense = jnp.pad(dense, ((0, pshape[0] - dense.shape[0]),
                                        (0, pshape[1] - dense.shape[1])))
            # zero anything outside the logical region
            r = jnp.arange(pshape[0])[:, None] < self.shape[0]
            c = jnp.arange(pshape[1])[None, :] < self.shape[1]
            dense = jnp.where(r & c, dense, 0)
            return jax.lax.with_sharding_constraint(dense, sharding)

        data = scatter(self.blocks, self.block_rows, self.block_cols)
        return BlockMatrix.from_array(
            data, self.shape, self.mesh,
            padding.canonical_spec(pshape, self.mesh),
            nnz=min(self.nnz, self.shape[0] * self.shape[1]),
            block_size=bs)

    def transpose(self) -> "BlockSparseMatrix":
        """Sᵀ: swap tile coordinates and transpose payloads (one device op);
        re-sorted row-major to keep the kernel invariants."""
        rows = np.asarray(self.block_cols)
        cols = np.asarray(self.block_rows)
        order = np.lexsort((cols, rows))
        rep = NamedSharding(self.mesh, P())
        blocks_t = jax.jit(  # matlint: disable=ML010 construction-time helper — arrays are born here, before any plan exists
            lambda b: jax.lax.with_sharding_constraint(
                jnp.transpose(b, (0, 2, 1))[jnp.asarray(order)], rep)
        )(self.blocks)
        return BlockSparseMatrix(
            blocks=blocks_t,
            block_rows=jax.device_put(rows[order].astype(np.int32), rep),
            block_cols=jax.device_put(cols[order].astype(np.int32), rep),
            shape=(self.shape[1], self.shape[0]),
            block_size=self.block_size, mesh=self.mesh)

    def norm(self, kind: str = "fro") -> float:
        """Matrix norm from the tile stack (tiles are unique by
        construction; zeros outside kept tiles contribute nothing)."""
        # float64 like the COO sibling: f32 squaring overflows at
        # ~1.8e19 magnitudes and f32 sums drift on large stacks
        b = np.asarray(self.blocks, np.float64)
        if kind == "fro":
            return float(np.sqrt((b * b).sum()))
        if kind == "l1":
            return float(np.abs(b).sum())
        if kind == "max":
            return float(np.abs(b).max()) if self.nnzb else 0.0
        raise ValueError(f"unknown norm kind {kind!r} "
                         "(expected 'fro', 'l1', or 'max')")

    def shard(self, mesh: Optional[Mesh] = None):
        """Distribute the tile stack over a mesh (each device holds
        ~nnzb/P tiles in its output row range) — the scale-out SpMM
        plan; see ops/spmm_sharded.py."""
        from matrel_tpu.ops.spmm_sharded import shard_block_sparse
        return shard_block_sparse(self, mesh)

    # -- lazy DSL -----------------------------------------------------------

    def expr(self):
        from matrel_tpu.ir import expr as E
        return E.MatExpr("sparse_leaf", (), tuple(self.shape),
                         min(self.nnz, self.shape[0] * self.shape[1]),
                         {"matrix": self})

    def multiply(self, other):
        from matrel_tpu.ir import expr as E
        return E.matmul(self.expr(), E.as_expr(other))

    def __repr__(self):
        return (f"BlockSparseMatrix(shape={self.shape}, bs={self.block_size}, "
                f"nnzb={self.nnzb}/{self.grid[0] * self.grid[1]})")
