"""How each kernel is recognised in a TPU profiler trace.

A device operation's name there is its HLO instruction, written out
whole.  The program's Pallas kernels carry no name of their own (their
instruction is named after the enclosing call), so each is told by the
signature of its ``tpu_custom_call``: the types and ranks of its operands
and of its result.
"""
from __future__ import annotations

import re

_CALL = re.compile(r"=\s*(.*?)\s+custom-call\((.*?)\),\s*"
                   r'custom_call_target="tpu_custom_call"')
_ARRAY = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")


def signature(op_name: str):
    """((dtype, rank) of each result, (dtype, rank) of each operand) of a
    Pallas custom call, or None for any other operation."""
    m = _CALL.search(op_name)
    if m is None:
        return None

    def arrays(text):
        return tuple((t, len(s.split(",")) if s else 0)
                     for t, s in _ARRAY.findall(text))
    return arrays(m.group(1)), arrays(m.group(2))


# (results, operands) of each kernel the rooflines read
SIGNATURES = {
    "paged_attention": [(
        (("f32", 4),),
        (("s32", 2), ("s32", 1), ("f32", 4), ("f32", 3), ("f32", 3)))],
    "flash_attention": [(
        (("f32", 3),), (("f32", 3), ("f32", 3), ("f32", 3)))],
    "fused_logprob": [
        ((("f32", 2),) * 3, (("s32", 2), ("f32", 2))),           # forward
        ((("f32", 2),), (("s32", 2), ("f32", 2), ("f32", 2),     # backward
                         ("f32", 2), ("f32", 2)))],
}


def matcher(kernel: str):
    sigs = set(SIGNATURES[kernel])
    return lambda op_name: signature(op_name) in sigs
