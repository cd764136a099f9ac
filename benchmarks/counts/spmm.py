"""Block-sparse x dense product: what the algorithm needs, from shapes.
Every stored tile is read once, the dense operand is read once, the
product is written once; every tile multiplies a (block_size x width)
panel. ``precision`` is the jax.lax.Precision the kernel's products run
at (``ops/pallas_spmm.py``: HIGHEST for float32 tiles, DEFAULT for
bfloat16): the peak that the operations are held to is the bf16 peak over
the MXU passes of that precision (``peaks.json`` ``mxu_passes``)."""


def counts(nnzb, block_size, rows, width, itemsize, precision):
    flops = 2 * nnzb * block_size * block_size * width
    nbytes = itemsize * (nnzb * block_size * block_size     # tiles
                         + rows * width                       # D
                         + rows * width)                      # the product
    return {"flops": flops, "bytes": nbytes, "precision": precision}
