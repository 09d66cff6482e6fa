"""Pallas TPU kernels. Each subpackage: kernel.py (pl.pallas_call +
BlockSpec), ops.py (jit wrapper), ref.py (pure-jnp oracle).  Every op
compiles the kernel by default; the CPU tests pass ``interpret=True``
themselves, and ``tests/test_chip_compile.py`` compiles them for a v5e.
No model calls these kernels: the models run their jnp paths."""
