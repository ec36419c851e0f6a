"""A Pallas kernel under a stable name.

The compiler names a kernel's custom call after whatever encloses it
(``closed_call.19``, ``checkpoint.15``), and the next refactor
renumbers that. A kernel's own ``name=`` goes into the custom call's
config and a ``jax.named_scope`` of the same name into the operation's
metadata, so a trace reduction finds the kernel whatever the compiler
called the call (PERF.md says where the name lands in a chip trace).
One name per role: layout variants of a kernel share it.
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl


def named_pallas_call(name: str, kernel, **kwargs):
    """``pl.pallas_call(kernel, name=name, **kwargs)`` whose call runs
    under ``jax.named_scope(name)``."""
    call = pl.pallas_call(kernel, name=name, **kwargs)

    def run(*operands):
        with jax.named_scope(name):
            return call(*operands)

    return run
