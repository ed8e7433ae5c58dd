"""What the serving steps may do with the stacked projection packs, read
from their compiled HLO.

The layer scan hands each projection GEMM its whole stacked float32 pack
``[L, K_pad, N_pad]`` and the layer's index, and the panel kernel reads
that layer's panels where they lie, rounding them to bf16 in VMEM.  An
expert bank ``[L, G, K_pad, N_pad]`` goes down whole the same way, and
XLA's grouped kernel reads it with the layer folded into its group axis.
``weight_copies`` lists every instruction that makes an array of a
stack's shape or of one layer's ``[K_pad, N_pad]`` slice of it (leading
unit dims aside): a
convert of the stack to bf16 (what XLA's default-precision dot does
before the layer loop), a slice of one layer copied out of it, or a copy
of the stack.  Custom calls (the kernels that read the stack) and
instructions that move nothing (parameters, tuples, bitcasts, loops) are
not counted.

``kernels_unpicked`` lists the panel-kernel custom calls that the
benchmark's GEMM rule (``chipbench.trace_reduce.gemm_op_names``) does not
pick: their time would be missing from ``gemm_roofline.prefill``.
"""
from __future__ import annotations

import re

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = \w+\[([\d,]*)\]\S*"
                    r" ([\w\-]+)\(")
_FREE = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
         "call", "conditional", "constant", "custom-call"}
_KERNEL = re.compile(r'kernel_name = "(panel_gemm\w*)"|'
                     r"^\s*(?:ROOT\s+)?%(panel_gemm[\w.\-]*) = ")


def stack_shapes(params, *, grouped: bool = False) -> set:
    """Shapes of every stacked float32 projection pack the layer scans
    read in place, and of one layer of each; with ``grouped``, of every
    stacked expert bank ``[L, G, K_pad, N_pad]`` and of one layer's
    ``[G, K_pad, N_pad]`` instead."""
    import jax
    from repro.core.packing import PackedWeight
    out = set()
    for pw in jax.tree.leaves(params,
                              is_leaf=lambda a: isinstance(a, PackedWeight)):
        if (type(pw) is PackedWeight and bool(pw.groups) == grouped
                and pw.data.ndim == (4 if grouped else 3)):
            out.add(tuple(pw.data.shape))
            out.add(tuple(pw.data.shape[1:]))
    return out


def weight_copies(hlo_text: str, shapes: set) -> list[str]:
    """Instructions that make an array of a stacked pack's shape or of
    one layer of it (the rule in the module docstring), as
    ``"name opcode [dims]"``."""
    bad = []
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, dims, opcode = m.groups()
        shape = tuple(int(d) for d in dims.split(",")) if dims else ()
        while shape[:1] == (1,):        # a layer as a stack of one
            shape = shape[1:]
        if shape in shapes and opcode not in _FREE:
            bad.append(f"{name} {opcode} [{dims}]")
    return bad


def panel_kernels(hlo_text: str) -> set:
    """Names of the panel-kernel custom calls in a compiled step."""
    names = set()
    for line in hlo_text.splitlines():
        m = re.match(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = .* custom-call\(", line)
        if m and 'custom_call_target="tpu_custom_call"' in line and re.search(
                r'op_name="[^"]*panel_gemm[^"]*"', line):
            names.add(m.group(1))
    return names


def kernels_unpicked(hlo_text: str) -> set:
    from chipbench import trace_reduce
    return panel_kernels(hlo_text) - trace_reduce.gemm_op_names(hlo_text)
