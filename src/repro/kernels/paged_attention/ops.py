"""jit'd wrappers for the paged decode attention kernels."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.paged_attention.kernel import (paged_attention_fwd,
                                                  paged_attention_pool_fwd)
from repro.kernels.paged_attention.ref import (paged_attention_pool_ref,
                                               paged_attention_ref)
from repro.kernels.platform import default_interpret


@partial(jax.jit, static_argnames=("window", "logit_cap", "scale"))
def paged_attention(q, pool_k, pool_v, block_table, lengths, *, window=0,
                    logit_cap=0.0, scale=None):
    """q: (B,H,hd) one decode token per sequence; pools (E,page,KV,hd);
    block_table (B,P) extent ids (holes -1); lengths (B,).
    Returns (B,H,hd_v)."""
    return paged_attention_fwd(q, pool_k, pool_v, block_table, lengths,
                               window=window, logit_cap=logit_cap,
                               scale=scale, interpret=default_interpret())


@partial(jax.jit, static_argnames=("k_plane", "v_plane", "window",
                                   "logit_cap", "scale"))
def paged_attention_pool(q, pool, block_table, lengths, *, k_plane, v_plane,
                         window=0, logit_cap=0.0, scale=None):
    """Zero-copy serving entry point: attend over two planes of ONE engine
    extent pool (E, page, n_planes, KV, hd) through the volume extent map.
    Standalone jit for direct callers; inside an outer jit (the serving
    decode program) call ``paged_attention_pool_fwd`` directly."""
    return paged_attention_pool_fwd(q, pool, block_table, lengths,
                                    k_plane=k_plane, v_plane=v_plane,
                                    window=window, logit_cap=logit_cap,
                                    scale=scale, interpret=default_interpret())


paged_attention_reference = paged_attention_ref
paged_attention_pool_reference = paged_attention_pool_ref
