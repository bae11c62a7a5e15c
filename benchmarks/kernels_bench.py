"""Kernel micro-benchmarks (interpret-mode wall-times are NOT TPU times;
reported for regression tracking of the reference paths)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.utils.compile_cache import place_compile_cache


def _t(fn, *args, iters=3):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
        (out[0] if isinstance(out, tuple) else out).block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6


def _dbs_rows(key):
    """One write + one read row per REGISTERED DBS kernel, with nominal
    achieved bytes/s (kernels/dbs ``dbs_write_bytes``/``dbs_read_bytes`` —
    implementation-independent, so the ratios compare across kernels)."""
    from repro.core import dbs
    from repro.kernels.dbs import (dbs_read_bytes, dbs_write_bytes,
                                   make_kernel)
    from repro.kernels.dbs.registry import available_kernels
    e, page, d, b = 257, 8, 64, 32          # +1 reserved scratch row
    ks = jax.random.split(key, 3)
    pool = jax.random.normal(ks[0], (e, page, d))
    payload = jax.random.normal(ks[1], (b, d))
    blocks = (jnp.arange(b, dtype=jnp.int32) * 3) % page
    dst = (jnp.arange(b, dtype=jnp.int32) * 5) % (e - 1)
    cow_src = jnp.where(jnp.arange(b) % 4 == 0,
                        (dst + 97) % (e - 1), -1).astype(jnp.int32)
    ok = jnp.arange(b) % 8 != 7
    ext = jnp.where(jnp.arange(b) % 5 == 0, -1, dst).astype(jnp.int32)
    itemsize = pool.dtype.itemsize
    wbytes = dbs_write_bytes(int(ok.sum()), int(((cow_src >= 0) & ok).sum()),
                             page, d, itemsize)
    rbytes = dbs_read_bytes(b, d, itemsize)
    rows = []
    for name in available_kernels():
        kern = make_kernel(name)
        wf = jax.jit(lambda p, pay, dd, cc, oo, bl, k=kern: k.write(
            p, dbs.WriteOps(dst=dd, cow_src=cc, ok=oo), pay, bl))
        rf = jax.jit(lambda p, ee, bl, k=kern: k.read(p, ee, bl))
        w_us = _t(wf, pool, payload, dst, cow_src, ok, blocks)
        r_us = _t(rf, pool, ext, blocks)
        rows.append({"bench": "kernel_dbs", "column": name, "layer": "B32",
                     "kind": "write", "us_per_call": w_us,
                     "bytes_per_s": wbytes / (w_us * 1e-6)})
        rows.append({"bench": "kernel_dbs", "column": name, "layer": "B32",
                     "kind": "read", "us_per_call": r_us,
                     "bytes_per_s": rbytes / (r_us * 1e-6)})
    return rows


def run():
    rows = []
    key = jax.random.PRNGKey(0)
    rows.extend(_dbs_rows(key))
    from repro.kernels.flash_attention.ops import flash_attention_reference
    q = jax.random.normal(key, (1, 512, 8, 64))
    k = jax.random.normal(key, (1, 512, 2, 64))
    v = jax.random.normal(key, (1, 512, 2, 64))
    rows.append({"bench": "kernel_ref", "column": "flash_attention",
                 "layer": "S512", "kind": "fwd",
                 "us_per_call": _t(lambda a, b, c: flash_attention_reference(
                     a, b, c), q, k, v)})
    from repro.kernels.paged_attention import paged_attention_reference
    pk = jax.random.normal(key, (64, 32, 2, 64))
    bt = jnp.arange(48).reshape(4, 12).astype(jnp.int32)
    ln = jnp.full((4,), 360, jnp.int32)
    qd = jax.random.normal(key, (4, 8, 64))
    rows.append({"bench": "kernel_ref", "column": "paged_attention",
                 "layer": "P12", "kind": "decode",
                 "us_per_call": _t(lambda a: paged_attention_reference(
                     a, pk, pk, bt, ln), qd)})
    return rows


def main():
    place_compile_cache()
    for r in run():
        bps = f"{r['bytes_per_s']:.3g}" if "bytes_per_s" in r else "-"
        print(f"{r['bench']},{r['column']},{r['layer']},{r['kind']},"
              f"{r['us_per_call']:.1f},{bps}")


if __name__ == "__main__":
    main()
