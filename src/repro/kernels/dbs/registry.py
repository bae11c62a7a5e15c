"""The DBS kernel registry: named data-plane implementations.

Mirrors the backend (core/backends.py ``register_backend``) and transport
(core/transport.py ``register_transport``) registries: a name resolves to a
``DBSKernel`` — one ``write`` (the whole write data plane of a batch: CoW
extent copies + payload block stores) and one ``read`` (the hole-masked
block gather) — and ``EngineConfig(kernel=...)`` threads the name through
every engine backend (fused/sharded/ring) instead of the old ``cow=``
string branch in ``fused._cow_apply``.

Built-ins:

========  ==================================================================
name      implementation
========  ==================================================================
pallas    ``dbs_rw`` Pallas kernels (rw_kernel.py): the whole step's data
          movement is kernel-owned (compiled on TPU, interpret elsewhere)
xla       ``dbs.apply_write_ops`` gather/scatter + the XLA hole-masked
          gather — the selectable reference path (the old ``cow="ref"``)
ref       pure-jnp mirror of the kernels' row-composition formulation
          (ref.py) — triangulates pallas against xla in the tests
copy      the PR-3 hybrid: ``dbs_copy`` Pallas CoW copy + XLA block
          scatter/gather (the old ``cow="pallas"`` data plane)
========  ==================================================================

All four are bit-identical on engine batches; the registry exists so the
choice is a config axis (and so embedders can register their own, like the
backend registry allows).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, Optional, Tuple

import jax.numpy as jnp

from repro.kernels.dbs import ops as _ops
from repro.kernels.platform import default_interpret


@dataclass(frozen=True)
class DBSKernel:
    """One registered data plane.

    ``write(pool, ops, payload, block_offsets) -> pool'`` applies a
    ``dbs.WriteOps`` batch to an (E, page, *payload) pool (the engine pool
    convention: the last row is the reserved scratch/dump extent).
    ``read(pool, ext, block_offsets) -> (B, *payload)`` gathers one block
    per lane, holes (``ext < 0``) masked to zeros.
    """
    name: str
    write: Callable
    read: Callable


_REGISTRY: Dict[str, DBSKernel] = {}


def register_kernel(name: str, write: Optional[Callable] = None, *,
                    read: Optional[Callable] = None,
                    override: bool = False) -> DBSKernel:
    """Register a ``DBSKernel`` under ``name`` from its two callables (or
    pass a ready ``DBSKernel`` as ``write``). Duplicate names raise (the
    uniform registry contract); embedders that mean to shadow a built-in
    pass ``override=True``."""
    if isinstance(write, DBSKernel):
        kern = write
    else:
        if write is None or read is None:
            raise ValueError("register_kernel needs write= and read= "
                             "callables (or a DBSKernel)")
        kern = DBSKernel(name=name, write=write, read=read)
    if name in _REGISTRY and not override:
        raise ValueError(
            f"duplicate kernel {name!r} (registered: "
            f"{', '.join(available_kernels())}); pass override=True "
            "to replace")
    _REGISTRY[name] = kern
    return kern


def available_kernels() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def make_kernel(name: str) -> DBSKernel:
    """Resolve the kernel registered under ``name``."""
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown kernel {name!r} (registered: "
            f"{', '.join(available_kernels())})") from None


def resolve_kernel_name(cfg) -> str:
    """``EngineConfig`` -> registry name, honouring the legacy ``cow`` axis:
    an explicit ``kernel`` wins; ``kernel="auto"`` follows ``cow``
    (``"pallas"``/``"ref"`` keep their historical meaning, ``"auto"`` picks
    the XLA reference where kernels would run interpreted — the CPU — and
    the compiled Pallas path everywhere else)."""
    kernel = getattr(cfg, "kernel", "auto")
    if kernel != "auto":
        return kernel
    cow = getattr(cfg, "cow", "auto")
    if cow == "pallas":
        return "pallas"
    if cow == "ref":
        return "xla"
    return "xla" if default_interpret() else "pallas"


# ---------------------------------------------------------------------------
# built-in entries
# ---------------------------------------------------------------------------
def _xla_write(pool, ops, payload, block_offsets):
    from repro.core import dbs
    return dbs.apply_write_ops(pool, ops, payload, block_offsets)


def _xla_read(pool, ext, block_offsets):
    got = pool[jnp.maximum(ext, 0), block_offsets]
    m = (ext >= 0).reshape(ext.shape + (1,) * (got.ndim - ext.ndim))
    return jnp.where(m, got, 0)


def _copy_write(pool, ops, payload, block_offsets):
    # the PR-3 hybrid: Pallas CoW copy, then the XLA block scatter.
    # write_pages guarantees cow_src>=0 implies a live lane, but gate on it
    # anyway so a hostile ops batch can never route a copy through a
    # clamped dst.
    live = ops.live()
    pool = _ops.dbs_copy_pool(pool, ops.cow_src, ops.dst,
                              (ops.cow_src >= 0) & live, scratch=True)
    # dead lanes scatter out of bounds and are dropped (write_pages note)
    drop_dst = jnp.where(live, jnp.maximum(ops.dst, 0), pool.shape[0])
    return pool.at[drop_dst, block_offsets].set(payload, mode="drop")


def _ref_write(pool, ops, payload, block_offsets):
    from repro.kernels.dbs.ref import dbs_rw_write_ref
    e, page = pool.shape[:2]
    flat = pool.reshape(e, page, -1)
    pay = payload.reshape(payload.shape[0], -1)
    src, dst, lane_of = _ops._route_writes(ops, page, block_offsets, e - 1)
    return dbs_rw_write_ref(flat, src, dst, lane_of, pay).reshape(pool.shape)


def _ref_read(pool, ext, block_offsets):
    from repro.kernels.dbs.ref import dbs_rw_read_ref
    e, page = pool.shape[:2]
    flat = pool.reshape(e, page, -1)
    out = dbs_rw_read_ref(flat, ext, block_offsets)
    return out.reshape((ext.shape[0],) + pool.shape[2:])


register_kernel("pallas", _ops.dbs_rw_write_pool, read=_ops.dbs_rw_read_pool)
register_kernel("xla", _xla_write, read=_xla_read)
register_kernel("ref", _ref_write, read=_ref_read)
register_kernel("copy", _copy_write, read=_xla_read)
