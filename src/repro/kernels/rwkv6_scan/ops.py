"""jit'd wrapper for the RWKV-6 chunked-scan kernel."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels.platform import default_interpret
from repro.kernels.rwkv6_scan.kernel import rwkv6_scan_fwd
from repro.kernels.rwkv6_scan.ref import rwkv6_scan_ref


@partial(jax.jit, static_argnames=("chunk",))
def rwkv6_scan(r, k, v, logw, u, *, chunk=64):
    """r,k,v,logw: (B,S,H,hd); u: (H,hd) -> (y (B,S,H,hd), s (B,H,hd,hd))."""
    return rwkv6_scan_fwd(r, k, v, logw, u, chunk=chunk,
                          interpret=default_interpret())


rwkv6_scan_reference = rwkv6_scan_ref
