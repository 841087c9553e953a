"""Pallas TPU kernels for the perf-critical hot spots.

``stack_ops``       — the PC VM's batched stack push/peek (the paper's
                      gather/scatter hot spot), driven by scalar-prefetched
                      stack pointers so each lane moves only its own row.
``flash_attention`` — causal GQA attention for train/prefill.
``flash_decode``    — single-token attention over long KV caches (decode).

Each package ships ``kernel.py`` (pl.pallas_call + BlockSpec), ``ops.py``
(jit wrapper: interpreted on CPU, compiled on TPU, see ``platform``) and
``ref.py`` (pure-jnp oracle); tests sweep shapes/dtypes and assert
allclose in interpret mode, and ``tests/test_tpu_compile.py`` compiles the
stack kernels for a described TPU.
"""
from . import flash_attention, flash_decode, stack_ops  # noqa: F401
