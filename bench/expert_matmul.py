"""The expert layer's grouped products in a TPU trace, and the work they
require.

The layer (``repro.models.ffn.experts_held``) runs three grouped products
per expert-layer pass: gate, up and down.  A trainer pass adds, for each,
the product against the transposed weights (``gmm``) and the weight
gradient (``tgmm``).  Both kernels are anonymous ``tpu_custom_call``s,
told apart from the other kernels by their operand signature: the tile
groups and tiles in use (``s32[tiles]``, ``s32[1]``) come first.

A layer scan hands each layer's weights to the kernels as a slice of
the stacked layers, which XLA copies whole for the custom call (into
fast memory where it fits): every held expert's matrix, hit or not.
Those copies are the products' cost too.  ``staging_ops`` finds them by
producer: from each ``gmm`` call's weight operand back through the
operations that made it (a slice, a copy and its asynchronous start, a
concatenation of slices), as long as each result is that weight or a
slice of it (rank 3, its last two dimensions); the stack of layers it
was sliced from is not followed.  Names repeat across programs, so an
operation is a producer only where both its name and its result type
(layout and memory space included) are the ones its user names.

Work is what the pairs require, whatever the kernels do with padding:
per (token, held expert) pair ``6 * D * F`` FLOPs (three products of
``2 * D * F``); per held expert hit, its three float32 matrices
(``12 * D * F`` bytes), and per pair its activations in and out (each
product reads its input row and writes its output row: ``4 * (3D + 3F)``
bytes).  A trainer pass does all of it three times: the forward, the
products against the transposed weights (reading the matrices again),
and the weight gradients (writing them).
"""
from __future__ import annotations

import re

from bench import kernels

F32 = 4

# (results, operands) of each grouped-matmul kernel
SIGNATURES = {
    "gmm": [((("f32", 2),),
             (("s32", 1), ("s32", 1), ("f32", 2), ("f32", 3)))],
    "tgmm": [((("f32", 3),),
              (("s32", 1), ("s32", 1), ("f32", 2), ("f32", 2)))],
}


def matcher(kernel: str):
    sigs = set(SIGNATURES[kernel])
    return lambda op_name: kernels.signature(op_name) in sigs


def is_grouped_matmul(op_name: str) -> bool:
    return kernels.signature(op_name) in \
        set(SIGNATURES["gmm"]) | set(SIGNATURES["tgmm"])


def _top_level(text: str):
    """``text`` split at commas outside brackets and braces."""
    out, depth, start = [], 0, 0
    for i, ch in enumerate(text):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == "," and depth == 0:
            out.append(text[start:i].strip())
            start = i + 1
    out.append(text[start:].strip())
    return out


def _closing(text: str, i: int) -> int:
    """Index of the bracket that closes the one at ``text[i]``."""
    depth = 0
    for j in range(i, len(text)):
        depth += text[j] in "([{"
        depth -= text[j] in ")]}"
        if depth == 0:
            return j
    return len(text) - 1


def parse_op(op_name: str):
    """(name, result type, [(operand type, operand name)]) of an HLO
    instruction as the trace names it."""
    name, _, rest = op_name.partition(" = ")
    rest = rest.lstrip()
    end = _closing(rest, 0) + 1 if rest.startswith("(") else rest.find(" ")
    rtype, rest = rest[:end], rest[end:]
    i = rest.find("(")
    if i < 0:
        return name, rtype, []
    args = []
    for a in _top_level(rest[i + 1:_closing(rest, i)]):
        t, _, n = a.rpartition(" ")
        if n.startswith("%"):
            args.append((t, n))
    return name, rtype, args


_ARRAY = re.compile(r"^\(*f32\[([\d,]+)\]")


def _dims(type_text: str):
    """Dimensions of an f32 array type, or of the first array in a tuple."""
    m = _ARRAY.match(type_text)
    return tuple(int(x) for x in m.group(1).split(",")) if m else None


def staging_ops(op_names):
    """The operations among ``op_names`` that stage a ``gmm`` call's
    weight operand: its producer, and theirs, while each result is that
    weight or a slice of it."""
    parsed = {op: parse_op(op) for op in op_names}
    by_name = {}
    for op, (name, _, _) in parsed.items():
        by_name.setdefault(name, []).append(op)
    todo = []
    for op in filter(matcher("gmm"), op_names):
        t, n = parsed[op][2][3]
        todo.append((t, n, _dims(t)[1:]))
    found = set()
    while todo:
        t, n, trailing = todo.pop()
        for op in by_name.get(n, ()):
            if op in found or parsed[op][1] != t:
                continue
            found.add(op)
            for at, an in parsed[op][2]:
                d = _dims(at)
                if d and len(d) == 3 and d[1:] == trailing:
                    todo.append((at, an, trailing))
    return found


def product_matcher(op_names):
    """Accepts the grouped-matmul calls among ``op_names`` and the
    operations that stage their weights."""
    staged = staging_ops(op_names)
    return lambda op: op in staged or is_grouped_matmul(op)


def product_work(*, pairs, hits, d_model, d_expert, passes=1):
    """(FLOPs, bytes) one expert-layer pass requires of its three
    products for ``pairs`` held pairs and ``hits`` held experts hit;
    ``passes`` 3 for a trainer pass."""
    D, F = d_model, d_expert
    flops = 6 * D * F * pairs
    nbytes = 12 * D * F * hits + F32 * (3 * D + 3 * F) * pairs
    return passes * flops, passes * nbytes


def least_seconds(peak, **work) -> float:
    flops, nbytes = product_work(**work)
    return max(flops / peak["flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def roofline_pct(least: float, seconds: float) -> float:
    """Least time over the time taken, in percent; above 100 the work
    is counted too high or the time misses part: an error."""
    pct = 100.0 * least / seconds
    if pct > 100.0:
        raise ValueError(f"grouped matmul: roofline share {pct:.1f}% > "
                         "100%: the work is counted too high or the time "
                         "misses part")
    return pct
