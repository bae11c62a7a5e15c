"""The kernels the per-layer rooflines read: how their events are named in
a chip trace, and the least bytes the users' operations need of them.

A v5e trace names each device operation by its HLO instruction, e.g. (read
off a trace by hand, longhorn-3r)::

    %stepped.8 = f32[4097,32,4096]{2,1,0:T(8,128)} custom-call(
      s32[64]{..} %a, s32[64]{..} %b, s32[2048]{..} %c,
      f32[4097,32,4096]{..} %pool, f32[64,4096]{..} %payload),
      custom_call_target="tpu_custom_call", ...,
      output_to_operand_aliasing={{}: (3, {})}, ...            (dbs_rw_write)

    %stepped.9 = f32[64,1,4096]{..} custom-call(s32[64]{..} %ext,
      s32[64]{..} %blk, f32[4097,32,4096]{..} %pool),
      custom_call_target="tpu_custom_call", ...                 (dbs_rw_read)

The kernels carry no name of their own there, so each is matched by its
calling convention: ``dbs_rw_write`` takes three int32 scalar-prefetch
vectors, the pool and the payload, and writes the pool in place (output
aliased to operand 3); ``dbs_rw_read`` takes two int32 vectors and the
pool. The instruction number and the dtypes may change; a kernel whose
convention changes shows as a roofline that reads nothing.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

_T = r"\w+\[[\d,]*\]\S*"        # an HLO operand type with its layout
WRITE_KERNEL = re.compile(
    rf"custom-call\(s32\[\d+\]\S* %\S+, s32\[\d+\]\S* %\S+, "
    rf"s32\[\d+\]\S* %\S+, {_T} %\S+, {_T} %\S+\), "
    r'custom_call_target="tpu_custom_call".*'
    r"output_to_operand_aliasing=\{\{\}: \(3, \{\}\)\}")
READ_KERNEL = re.compile(
    rf"custom-call\(s32\[\d+\]\S* %\S+, s32\[\d+\]\S* %\S+, {_T} %\S+\), "
    r'custom_call_target="tpu_custom_call"')
def _head(op: str) -> str:
    """``%name opcode type`` of an HLO instruction's text."""
    name, sep, rest = op.partition(" = ")
    if not sep:
        return op[:120]
    depth, i = 0, 0
    for i, ch in enumerate(rest):      # the result type may be a tuple
        depth += ch == "("
        depth -= ch == ")"
        if ch == " " and depth == 0:
            break
    typ, opcode = rest[:i], rest[i + 1:].split("(", 1)[0]
    if typ.startswith("("):
        typ = "(" + ", ".join(t.split("{")[0] for t in typ[1:-1].split(", ")
                              if "[" in t) + ")"
    return f"{name} {opcode} {typ.split('{')[0]}"


def label(op: str) -> str:
    """A short name for a device operation's HLO text: the kernel's name
    where it is one, then the instruction, its opcode and result type."""
    kern = kernel_of(op)
    short = _head(op)
    return f"{kern} {short}" if kern else short


def kernel_of(op: str) -> Optional[str]:
    if WRITE_KERNEL.search(op):
        return "dbs_rw_write"
    if READ_KERNEL.search(op):
        return "dbs_rw_read"
    return None


def write_user_bytes(blocks: int, geometry: Dict) -> int:
    """A block write to R replicas: one payload read, R block writes."""
    return blocks * (1 + int(geometry["n_replicas"])) \
        * int(geometry["payload_elems"])


def read_user_bytes(blocks: int, geometry: Dict) -> int:
    """A block read: one block read, one block written out."""
    return blocks * 2 * int(geometry["payload_elems"])
