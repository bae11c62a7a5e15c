"""jit'd public wrapper: layout adaptation + backend dispatch."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.platform import default_interpret


@partial(jax.jit, static_argnames=("window", "logit_cap", "scale",
                                   "block_q", "block_k"))
def flash_attention(q, k, v, q_pos=None, k_pos=None, *, window=0,
                    logit_cap=0.0, scale=None, block_q=256, block_k=256):
    """Model-layout entry: q (B,S,H,hd); k,v (B,S,KV,hd) -> (B,S,H,hd).

    Positions are suffix-aligned (standard causal LM); q_pos/k_pos args are
    accepted for API parity with the XLA paths and ignored (they are always
    arange in train/prefill).
    """
    qt = q.swapaxes(1, 2)
    kt = k.swapaxes(1, 2)
    vt = v.swapaxes(1, 2)
    out = flash_attention_fwd(qt, kt, vt, causal=True, window=window,
                              logit_cap=logit_cap, scale=scale,
                              block_q=block_q, block_k=block_k,
                              interpret=default_interpret())
    return out.swapaxes(1, 2).astype(q.dtype)


def flash_attention_reference(q, k, v, *, window=0, logit_cap=0.0, scale=None):
    qt = q.swapaxes(1, 2)
    kt = k.swapaxes(1, 2)
    vt = v.swapaxes(1, 2)
    out = attention_ref(qt, kt, vt, causal=True, window=window,
                        logit_cap=logit_cap, scale=scale)
    return out.swapaxes(1, 2).astype(q.dtype)
