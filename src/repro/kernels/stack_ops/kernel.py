"""Pallas TPU kernels for the PC VM's batched stack traffic.

The paper identifies per-variable stack pushes/pops as the cost of
materializing recursion: a push scatters each active lane's value to its
own depth; a pop/peek gathers from per-lane depths.  XLA lowers these to
generic scatter/gather, which on TPU serializes badly.  The TPU-native
formulation used here drives the data movement from *scalar-prefetched*
stack pointers: the grid iterates over batch lanes, and each lane's
``BlockSpec`` index_map picks exactly the ``[1, F]`` stack row addressed
by ``ptr[z]`` — so each push/peek moves only ``F`` elements per lane
between HBM and VMEM (the minimum), with no scatter at all.

Layout: stacks are ``[D, Z, 1, F]`` and values ``[Z, 1, F]``.  Mosaic
requires the last two dimensions of a block to be multiples of ``(8,
128)`` or equal to the array's own; the explicit unit axis makes a lane's
``(1, F)`` row a legal block for every ``F`` (1 for scalars, 2 for PRNG
keys, ``dim`` for positions), with depth and lane squeezed out of the
block.  The mask is prefetched as ``int32``: scalar memory holds no
booleans.  Masked pushes select between the new value and the resident
row inside VMEM (select is free on the VPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SQ = pl.squeezed


# ---------------------------------------------------------------------------
# push
# ---------------------------------------------------------------------------


def _push_kernel(ptr_ref, mask_ref, val_ref, row_in_ref, row_out_ref):
    z = pl.program_id(0)
    active = mask_ref[z] != 0
    # val/row blocks are [1, F] for this lane's target depth.
    row_out_ref[...] = jnp.where(active, val_ref[...], row_in_ref[...])


def masked_push(stack: jax.Array, ptr: jax.Array, val: jax.Array,
                mask: jax.Array, *, interpret: bool) -> jax.Array:
    """stack: [D, Z, 1, F]; ptr, mask: [Z]; val: [Z, 1, F]."""
    d, z, _, f = stack.shape
    clipped = jnp.clip(ptr, 0, d - 1).astype(jnp.int32)
    # Drop pushes whose pointer is out of range (VM guards this anyway).
    live = jnp.logical_and(mask, jnp.logical_and(ptr >= 0, ptr < d))

    row = pl.BlockSpec(
        (_SQ, _SQ, 1, f), lambda i, ptr, mask: (ptr[i], i, 0, 0)
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # ptr, mask
        grid=(z,),
        in_specs=[
            pl.BlockSpec((_SQ, 1, f), lambda i, ptr, mask: (i, 0, 0)),
            row,  # resident stack row at [ptr[i], i]
        ],
        out_specs=row,
    )
    fn = pl.pallas_call(
        _push_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(stack.shape, stack.dtype),
        # operand order includes the scalar-prefetch args: the stack (arg 3)
        # aliases the output buffer, so unwritten rows are never copied.
        input_output_aliases={3: 0},
        name="stack_masked_push",
        interpret=interpret,
    )
    return fn(clipped, live.astype(jnp.int32), val.astype(stack.dtype), stack)


# ---------------------------------------------------------------------------
# peek (pop's data movement; pointer arithmetic stays in the VM)
# ---------------------------------------------------------------------------


def _peek_kernel(ptr_ref, row_ref, out_ref):
    out_ref[...] = row_ref[...]


def masked_peek(stack: jax.Array, ptr: jax.Array, *,
                interpret: bool) -> jax.Array:
    """stack: [D, Z, 1, F]; ptr: [Z] -> [Z, 1, F] = stack[ptr[z], z]."""
    d, z, _, f = stack.shape
    clipped = jnp.clip(ptr, 0, d - 1).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(z,),
        in_specs=[
            pl.BlockSpec((_SQ, _SQ, 1, f), lambda i, ptr: (ptr[i], i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((_SQ, 1, f), lambda i, ptr: (i, 0, 0)),
    )
    fn = pl.pallas_call(
        _peek_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((z, 1, f), stack.dtype),
        name="stack_masked_peek",
        interpret=interpret,
    )
    return fn(clipped, stack)
