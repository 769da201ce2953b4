"""Pallas TPU kernels for the paper's compute hot-spots.

Each kernel package has:
* ``kernel.py`` — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling,
* ``ops.py``    — jit'd public wrapper (padding, shape plumbing),
* ``ref.py``    — pure-jnp oracle the tests sweep against.

BlockSpecs are written for the TPU memory hierarchy (HBM -> VMEM ->
MXU/VPU), and the kernels compile for and run on the chip
(``chip_smoke.py`` runs convcore + postproc there at 416x416).  On a
CPU they run only with ``interpret=True``, which is how the tests sweep
them against the references.
"""
