"""Pallas TPU kernels for the compute hot-spots (DESIGN.md §7).

TPU is the *target*; on CPU every kernel runs in ``interpret=True`` mode and
is validated against the pure-jnp oracles in ``ref.py``. ``ops.py`` holds the
jit'd public wrappers (padding, dtype plumbing, interpret-mode dispatch).
The 3SFC encode's pytree reductions need no kernel: they run leaf by leaf
in plain XLA (``core.flat``), and ``ref.py`` keeps their oracles.

  sign_quant   — signSGD sign+scale extraction, int8 wire format
  topk_mask    — DGC threshold-select sparsifier (TPU-native top-k)
  ssd_chunk    — Mamba2 SSD intra-chunk kernel (MXU matmuls per chunk)
"""
