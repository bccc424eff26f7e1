"""The one ``shard_map`` every chain program in ``repro`` is built with.

The chain, multi-object, repair and checkpoint programs run Pallas kernels
inside ``shard_map``. With varying-manual-axes checking on, a
``pallas_call`` needs a ``vma`` on every output shape, and the Pallas
interpreter (the CPU path the tests take) then rejects the mixed-vma
operands of its own block slicing. The check is therefore off for these
programs; each one states its sharding through ``in_specs``/``out_specs``.
"""
from __future__ import annotations

import jax


def shard_map(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` with varying-manual-axes checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
