"""What the paged serving steps may do with the KV pool, read from their
compiled HLO.

The steps index the stacked pool ``[L, P, page, *feat]`` in place: per
layer they scatter the new tokens into it and gather the slots' view out
of it.  ``pool_copies`` lists every instruction that moves an array of
at least one layer pool's elements and is not one of those:

* instructions under the KV path's scopes (``kv_write``, ``kv_view``),
  and ``copy`` instructions with no ``op_name`` (the layout copies XLA
  inserts, such as a copy of the donated pool around the layer loop);
* allowed: the in-place scatter (a ``kv_write/scatter`` that is not a
  copy), and the slots' view — its gather, a copy of that gather, and
  its reshape and pad (``kv_view/gather``, ``kv_view/reshape``,
  ``kv_view/.../pad``).  The view is sized by slots x max_len, not by the
  pool; when the pool holds exactly slots x max_len tokens a decode
  step's view has the pool's size, and only its ops tell it apart.
"""
from __future__ import annotations

import math
import re

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = (\w+)\[([\d,]*)\]\S*"
                    r" ([\w\-]+)\((.*?)\)(?:,|$)")
_OPNAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_KV = re.compile(r"(?:^|/)kv_(?:write|view)(?:/|$)")
_FREE = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
         "call", "conditional", "constant"}
_COPIES = {"copy", "copy-start", "copy-done"}
_VIEW = re.compile(r"kv_view/(?:gather|reshape|.*pad)$")


def _instructions(hlo_text: str) -> dict:
    """``{name: (dtype, elements, opcode, op_name, first operand)}`` of
    every array-valued instruction."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, dtype, dims, opcode, operands = m.groups()
        n = math.prod(int(d) for d in dims.split(",")) if dims else 1
        op = _OPNAME.search(line)
        src = _OPERAND.search(operands)
        out[name] = (dtype, n, opcode, op.group(1) if op else None,
                     src.group(1) if src else None)
    return out


def _is_view(instrs: dict, name: str) -> bool:
    _, _, opcode, op_name, src = instrs[name]
    if op_name is None or not _VIEW.search(op_name):
        return False
    if opcode not in _COPIES:
        return True
    # a copy is the view's only as a relayout of the gathered view
    while src in instrs and instrs[src][2] == "bitcast":
        src = instrs[src][4]
    if src not in instrs:
        return False
    _, _, src_opcode, src_op_name, _ = instrs[src]
    return (src_opcode in ("gather", "fusion") and src_op_name is not None
            and src_op_name.endswith("kv_view/gather"))


def pool_copies(hlo_text: str, layer_elements: int) -> list[str]:
    """Instructions of at least ``layer_elements`` elements that slice,
    copy or write back the pool (the rule in the module docstring), as
    ``"name opcode op_name"``."""
    instrs = _instructions(hlo_text)
    bad = []
    for name, (_, n, opcode, op_name, _) in instrs.items():
        if n < layer_elements or opcode in _FREE:
            continue
        kv = op_name is not None and _KV.search(op_name)
        if not kv and not (op_name is None and opcode in _COPIES):
            continue
        if (kv and op_name.endswith("kv_write/scatter")
                and opcode not in _COPIES):
            continue
        if _is_view(instrs, name):
            continue
        bad.append(f"{name} {opcode} {op_name}")
    return bad


def step_texts(eng, *, slots: int, chunk: int, page_size: int,
               num_pages: int, sharding=None) -> dict:
    """Compiled HLO text of the engine's paged prefill-chunk step (one
    ``chunk``-wide row), decode step (``slots`` rows) and a 4-deep decode
    megastep, with the pool and every input placed by ``sharding`` when
    one is given."""
    import jax
    import jax.numpy as jnp
    from repro.runtime import kv_cache as KV

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    i32 = jnp.int32
    pps = eng.max_len // page_size
    pages = {name: sds((eng.cfg.num_layers, num_pages, page_size, *feat), dt)
             for name, (feat, dt) in KV.leaf_specs_for(eng.cfg).items()}
    steps = eng._paged_steps(fallback=False)
    prefill = steps["prefill"].lower(
        eng.params, pages, sds((1, pps), i32), sds((1,), i32),
        sds((1, chunk), i32), sds((), i32), page_size=page_size)
    decode = steps["decode"].lower(
        eng.params, pages, sds((slots, pps), i32), sds((slots,), i32),
        sds((slots,), bool), sds((slots,), i32), page_size=page_size)
    megastep = steps["megastep"].lower(
        eng.params, pages, sds((slots, pps), i32), sds((slots,), i32),
        sds((slots,), bool), sds((slots,), i32), sds((), i32),
        page_size=page_size, max_depth=4)
    return {name: low.compile().as_text() for name, low in
            (("prefill", prefill), ("decode", decode),
             ("megastep", megastep))}


def layer_pool_elements(eng, *, num_pages: int, page_size: int) -> int:
    """Elements of one layer's pool of one cached tensor (K or V; MLA's
    latent rows)."""
    from repro.runtime import kv_cache as KV
    feat, _ = next(iter(KV.leaf_specs_for(eng.cfg).values()))
    return num_pages * page_size * math.prod(feat)
