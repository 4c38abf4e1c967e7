"""The scoped-x64 call wrapper, and the two jax names the kernels share.

``enable_x64`` and ``shard_map`` are ``jax.enable_x64`` and
``jax.shard_map`` (jax 0.9); they are re-exported here so every kernel
module imports them from one place next to :func:`x64_scoped`.
"""

from __future__ import annotations

import functools

import jax

enable_x64 = jax.enable_x64
shard_map = jax.shard_map


def x64_scoped(fn):
    """Run every invocation of ``fn`` under ``enable_x64(True)``.

    The kernels write their uint64 blocks inside scoped ``enable_x64``
    contexts; on jax versions where lowering reads the flag at the
    jit-call boundary rather than at trace time, the scoped block alone
    fails stablehlo verification ("shift_left op requires compatible
    types") — the *call* must sit inside the scope so trace, lower, and
    compile all see x64.  Wrapping only the u64-bearing entry points
    keeps the flag out of the global config (which would change dtype
    inference package-wide)."""
    @functools.wraps(fn)
    def call(*args, **kwargs):
        with enable_x64(True):
            return fn(*args, **kwargs)

    return call
