"""Backend probe for the graftkern Pallas layer.

``interpret_default()`` is THE one place the interpret/compiled decision
lives: every production kernel passes ``interpret=interpret_default()``
to its ``pallas_call`` so the choice follows the backend that actually
runs the program — Mosaic-compiled on a TPU, the Pallas interpreter
everywhere else (which is what keeps tier-1 CPU-runnable).  graftlint's
``pallas-interpret-in-prod`` rule (analysis/padshape.py) flags any
``interpret=True`` literal outside this module so a debug hack can
never pin a TPU deployment to the interpreter silently.
"""

from __future__ import annotations

import jax


def interpret_default() -> bool:
    """True when Pallas kernels must run under the interpreter.

    Read at TRACE time, never at import: ``jax.default_backend()``
    initializes the platform client, and importing the kern package must
    stay side-effect-free (same discipline as ops/ed25519._jit_donated —
    a second process probing a chip that another process holds would
    otherwise fail at import)."""
    return jax.default_backend() != "tpu"


def interpret_probe() -> bool:
    """Run a one-tile kernel in FORCED interpreter mode and check the
    result — validates the interpreter itself (tests and kern_gate run
    this even on a machine with a TPU attached, where
    interpret_default() would say False)."""
    import numpy as np
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def _k(x_ref, o_ref):
        o_ref[:] = x_ref[:] + 1

    out = pl.pallas_call(
        _k,
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.int32),
        # Deliberately forced: this probe validates the INTERPRETER,
        # independent of the backend; production kernels select via
        # interpret_default().
        # graftlint: disable=pallas-interpret-in-prod
        interpret=True,
    )(jnp.zeros((8, 128), jnp.int32))
    return bool((np.asarray(out) == 1).all())
