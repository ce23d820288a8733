"""jit wrapper: model-layout (b,s,h,d)/(b,t,g,d) → kernel layout, GQA
expansion.  ``interpret=True`` runs the kernel body in the Pallas
interpreter (CPU tests); the default compiles it for the TPU."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_fwd_pallas


def flash_attention_fwd(q, k, v, *, causal: bool = True,
                        window: int | None = None, cq: int = 128,
                        ck: int = 128, interpret: bool = False):
    """q: (b,s,h,d); k/v: (b,t,g,d) → (b,s,h,d) via the Pallas kernel."""
    b, s, h, d = q.shape
    t, g = k.shape[1], k.shape[2]
    r = h // g
    qk = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kk = jnp.repeat(k, r, axis=2).transpose(0, 2, 1, 3).reshape(b * h, t, d)
    vk = jnp.repeat(v, r, axis=2).transpose(0, 2, 1, 3).reshape(b * h, t, d)
    o = flash_fwd_pallas(qk, kk, vk, causal=causal, window=window,
                         cq=min(cq, s), ck=min(ck, t), interpret=interpret)
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3)
