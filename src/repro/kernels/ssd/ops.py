"""jit wrapper mapping the model's SSD layout onto the Pallas kernel,
with head-slab splitting to bound VMEM (r per slab ≤ 8).  ``interpret=True``
runs the kernel body in the Pallas interpreter (CPU tests); the default
compiles it for the TPU."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.ssd.kernel import ssd_diag_pallas

_MAX_R = 8


def ssd_diag_block(xc, dtc, cum, bc, cc, r: int,
                   interpret: bool = False):
    """Model layout: xc (b,c,q,h,p), dtc/cum (b,c,q,h), bc/cc (b,c,q,g,n)
    with h = g·r.  Returns y_diag (b,c,q,h,p)."""
    b, c, q, h, p = xc.shape
    g = bc.shape[3]
    xg = xc.reshape(b, c, q, g, r, p)
    dtg = dtc.reshape(b, c, q, g, r)
    cumg = cum.reshape(b, c, q, g, r)
    outs = []
    for lo in range(0, r, _MAX_R):
        hi = min(lo + _MAX_R, r)
        y = ssd_diag_pallas(xg[..., lo:hi, :], dtg[..., lo:hi],
                            cumg[..., lo:hi], bc, cc, interpret=interpret)
        outs.append(y)
    y = jnp.concatenate(outs, axis=4) if len(outs) > 1 else outs[0]
    return y.reshape(b, c, q, h, p)
