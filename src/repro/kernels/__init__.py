"""Pallas TPU kernels for the compute hot-spots Weld optimizes.

Each kernel <name>.py carries a pl.pallas_call with explicit BlockSpec
VMEM tiling; ops.py holds the jit'd public wrappers; ref.py the pure-jnp
oracles.  All kernels validate in interpret mode on CPU (the dry-run
and CPU benchmarks use the ref path; the kernels are the TPU target).

Kernel inventory and the Weld construct each one lowers:
  * filter_reduce   — predicated single-pass merger (Listing 10 / TPC-H Q6)
  * segment_reduce  — vecmerger/dictmerger via one-hot MXU matmul
                      (atomic-free "global" builder strategy, §7.7)
  * fused_adamw     — the framework's weld-fused optimizer elementwise chain
  * tiled_matmul    — loop tiling (paper Table 3) as BlockSpec VMEM tiling
  * flash_attention — chunked online-softmax attention (VMEM-resident tiles)
"""
