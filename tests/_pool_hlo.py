"""Find whole-pool moves of the paged KV cache in compiled HLO text.

A paged decode step that keeps its page pools in place writes one row
per slot (a scatter) and reads pages through the kernel; it never
copies, slices or re-stacks a whole pool. This helper lists every
``copy`` (sync or async), ``dynamic-slice`` and ``dynamic-update-slice``
whose result has a pool's shape — the layer-stacked ``(R, P, page,
Hkv, Dh)`` or one layer's ``(P, page, Hkv, Dh)`` — whatever its operand
is (an argument, a loop value), and fusions named after those ops,
which is how the TPU compiler prints a fused slice or update.
"""

import re

import jax

from repro.serve import pages as PG

_DEF = re.compile(r"%(?P<name>[\w.-]+) = \w+\[(?P<dims>[\d,]*)\]\S* "
                  r"(?P<op>[\w-]+)\(")
_MOVES = ("copy", "dynamic-slice", "dynamic-update-slice")


def pool_shapes(cfg, cache_shapes) -> set:
    """Every KV pool shape of a paged cache: stacked and per layer."""
    out = set()
    flags = jax.tree.leaves(PG.kv_leaf_flags(cfg))
    for paged, s in zip(flags, jax.tree.leaves(cache_shapes)):
        if paged:
            out.add(tuple(s.shape))
            out.add(tuple(s.shape[-4:]))
    return out


def pool_moves(hlo_text: str, shapes: set) -> list:
    """Lines of ``hlo_text`` that copy, slice or update a whole pool."""
    found = []
    for ln in hlo_text.splitlines():
        m = _DEF.search(ln)
        if not m:
            continue
        dims = tuple(int(d) for d in m["dims"].split(",") if d)
        if dims not in shapes:
            continue
        op, name = m["op"], m["name"].replace("_", "-")
        if op in _MOVES or op == "copy-done" or (
                op == "fusion" and any(w in name for w in _MOVES)):
            found.append(ln.strip())
    return found
