"""Wrappers for the stack kernels in the VM's stack layout.

The VM keeps a stack as ``[D, Z, ...]`` for values of any rank; these
wrappers flatten the value's features to ``F`` and add the unit axis the
kernels' ``[D, Z, 1, F]`` blocks need.  The kernels run interpreted on
CPU (where the suite validates them) and compiled on TPU
(:func:`repro.kernels.platform.interpret_mode`).  The VM's default
(`use_kernel=False`) uses the jnp reference, which XLA compiles to
scatter/gather.

Under lane sharding (`VMConfig.mesh`) stack traffic stays strictly
per-lane, so :func:`shard_local` wraps the same wrappers in `shard_map`:
each device runs one `pallas_call` over its own lane slice — no
cross-device traffic, bit-exact with the unsharded kernel and with the
XLA scatter/gather path.
"""
from __future__ import annotations

import functools
import math

import jax
from jax.sharding import PartitionSpec as P

from ..platform import interpret_mode
from . import kernel


def masked_push(stack: jax.Array, ptr: jax.Array, val: jax.Array,
                mask: jax.Array) -> jax.Array:
    """stack: [D, Z, ...]; ptr/mask: [Z]; val: [Z, ...]."""
    d, z = stack.shape[:2]
    f = math.prod(stack.shape[2:])
    out = kernel.masked_push(
        stack.reshape(d, z, 1, f), ptr, val.reshape(z, 1, f), mask,
        interpret=interpret_mode(),
    )
    return out.reshape(stack.shape)


def masked_peek(stack: jax.Array, ptr: jax.Array) -> jax.Array:
    """stack: [D, Z, ...]; ptr: [Z] -> [Z, ...]."""
    d, z = stack.shape[:2]
    f = math.prod(stack.shape[2:])
    out = kernel.masked_peek(
        stack.reshape(d, z, 1, f), ptr, interpret=interpret_mode()
    )
    return out.reshape(stack.shape[1:])


@functools.lru_cache(maxsize=None)
def shard_local(mesh):
    """Shard-local ``(masked_push, masked_peek)`` for a 1-D lane mesh.

    Each returned callable has the same signature/semantics as the
    module-level wrapper it wraps, but runs one kernel per device over
    that device's lane slice: stacks are ``[depth, lanes, ...]`` with the
    lane axis sharded, pointers/masks/values shard their leading lane
    axis, and feature dims stay unpartitioned.  ``check_vma=False``
    because Pallas calls don't participate in shard_map's replication
    inference.  Cached per mesh so VM instances and tests share the
    wrapped callables (and their jit caches).
    """
    axis = mesh.axis_names[0]
    push = jax.shard_map(
        masked_push,
        mesh=mesh,
        in_specs=(P(None, axis), P(axis), P(axis), P(axis)),
        out_specs=P(None, axis),
        check_vma=False,
    )
    peek = jax.shard_map(
        masked_peek,
        mesh=mesh,
        in_specs=(P(None, axis), P(axis)),
        out_specs=P(axis),
        check_vma=False,
    )
    return push, peek
