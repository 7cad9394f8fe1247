"""Parsers over compiled HLO text: collectives, replica groups, aliases.

One shared parser for everything in the repo that inspects
``jitted.lower(...).compile().as_text()`` — the program linter
(``analysis.rules`` / ``tools/lint_programs.py``) and the HLO assertions
in ``tests/test_wire2d.py`` / ``tests/test_collectives.py`` /
``tests/test_api.py``, which previously each hand-rolled their own
regex line scans.

The unit of analysis is the :class:`Collective`: one cross-device HLO
instruction with its result dtype/shape and its concrete device
grouping.  Replica groups come in two textual forms and both are
materialized to explicit device-id lists:

* brace lists — ``replica_groups={{0,4},{1,5}}``;
* iota lists — ``replica_groups=[4,2]<=[2,4]T(1,0)``: reshape
  ``iota(prod)`` to the source dims, transpose by the permutation, then
  reshape to ``[n_groups, group_size]`` rows.

``crosses_data_axis`` classifies a grouping against the repo's row-major
``(data, model)`` meshes (``jax.make_mesh((D, M))`` assigns device id
``d * M + m`` — asserted in ``tests/test_analysis.py``).
"""
from __future__ import annotations

import dataclasses
import math
import re
from typing import List, Optional, Sequence, Tuple

# HLO ops that move data across devices.  "-start" covers the async
# forms ("-done" carries no shape/groups of its own and is not counted —
# one launch, one entry).
COLLECTIVE_OPS = ("all-reduce", "all-gather", "all-to-all",
                  "reduce-scatter", "collective-permute",
                  "collective-broadcast")

# Results smaller than this are treated as scalar-class traffic (loss /
# gnorm scalars, per-leaf amax grids, feature extremes) by the dtype-flow
# rules; a gradient-sized leaf is always far above it.
SCALAR_MAX = 256

# result is either `dtype[dims]{layout}` or a tuple `(dtype[..]{..}, ...)`
# (async pairs, multi-operand all-to-all): skip lazily to the op name.
# TPU layouts nest parentheses (`{1,0:T(8,128)(4,1)}`), so the skip
# cannot stop at the first `)`.
_COLLECTIVE_RE = re.compile(
    r"=\s+\(?(\w+)\[([\d,]*)\].*?\s("
    + "|".join(COLLECTIVE_OPS) + r")(-start)?\(")
_BRACE_GROUPS_RE = re.compile(r"replica_groups=\{(\{[\d{},]*\})\}")
_IOTA_GROUPS_RE = re.compile(
    r"replica_groups=\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?")
_SOURCE_TARGET_RE = re.compile(r"source_target_pairs=\{([\d{},]*)\}")
_FRAME_TABLES_RE = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*",
    re.M)


def strip_metadata(hlo: str) -> str:
    """Strip source-location noise from compiled HLO text, for
    program-identity comparisons: the stack-frame tables at the top of
    the module (``FileNames`` ... ``StackFrames``), ``metadata={...}``
    blocks and every quoted string (op names embed auto-numbered trace
    paths that are not the program)."""
    hlo = _FRAME_TABLES_RE.sub("", hlo)
    hlo = re.sub(r"metadata=\{[^}]*\}", "", hlo)
    return re.sub(r'"[^"]*"', '""', hlo)


def _transpose_reshape_iota(dims: Sequence[int], reshape: Sequence[int],
                            perm: Optional[Sequence[int]]
                            ) -> List[List[int]]:
    """Materialize an iota replica-group list without numpy: iota over
    ``prod(reshape)``, laid out in ``reshape`` order, transposed by
    ``perm``, re-read as ``dims`` = [n_groups, group_size...]."""
    total = math.prod(reshape)
    ids = list(range(total))
    if perm:
        # strides of the source layout, then walk the transposed order
        strides = [0] * len(reshape)
        acc = 1
        for i in range(len(reshape) - 1, -1, -1):
            strides[i] = acc
            acc *= reshape[i]
        tdims = [reshape[p] for p in perm]
        tstrides = [strides[p] for p in perm]
        out = []
        idx = [0] * len(tdims)
        for _ in range(total):
            out.append(sum(i * s for i, s in zip(idx, tstrides)))
            for d in range(len(tdims) - 1, -1, -1):
                idx[d] += 1
                if idx[d] < tdims[d]:
                    break
                idx[d] = 0
        ids = out
    group_size = total // dims[0]
    return [ids[g * group_size:(g + 1) * group_size]
            for g in range(dims[0])]


def parse_replica_groups(line: str) -> Optional[List[List[int]]]:
    """Concrete device-id groups of one HLO line, or None when the line
    carries no grouping (callers decide whether that means "global")."""
    m = _BRACE_GROUPS_RE.search(line)
    if m:
        return [[int(x) for x in grp.split(",")]
                for grp in re.findall(r"\{([\d,]+)\}", m.group(1))]
    m = _IOTA_GROUPS_RE.search(line)
    if m:
        dims = [int(x) for x in m.group(1).split(",")]
        reshape = [int(x) for x in m.group(2).split(",")]
        perm = ([int(x) for x in m.group(3).split(",")]
                if m.group(3) else None)
        return _transpose_reshape_iota(dims, reshape, perm)
    m = _SOURCE_TARGET_RE.search(line)
    if m:
        # collective-permute: each {src,dst} pair is a 2-device group
        return [[int(x) for x in pair.split(",")]
                for pair in re.findall(r"\{([\d,]+)\}", m.group(1))]
    return None


@dataclasses.dataclass(frozen=True)
class Collective:
    """One cross-device instruction in a compiled module."""
    kind: str                 # "all-reduce", "all-gather", ...
    dtype: str                # HLO dtype of the result ("f32", "s8", ...)
    dims: Tuple[int, ...]
    groups: Optional[Tuple[Tuple[int, ...], ...]]  # None = unknown/global
    line: str                 # the stripped source line (diagnostics)

    @property
    def numel(self) -> int:
        return math.prod(self.dims) if self.dims else 1

    def crosses_data_axis(self, model_size: int) -> bool:
        """Does this collective move bytes between data-axis rows of a
        row-major ``(data, model)`` mesh?  Unknown grouping counts as
        crossing — the conservative reading every rule wants."""
        if self.groups is None:
            return True
        return any(len({i // model_size for i in grp}) > 1
                   for grp in self.groups)


def parse_collectives(hlo: str) -> List[Collective]:
    """Every collective instruction of a compiled module, in program
    order.  Tuple-shaped results (async pairs, multi-operand all-to-all)
    report the first element's dtype/shape — one launch, one entry."""
    out = []
    for raw in hlo.splitlines():
        line = raw.strip()
        m = _COLLECTIVE_RE.search(line)
        if m is None:
            continue
        dims = tuple(int(x) for x in m.group(2).split(",") if x)
        groups = parse_replica_groups(line)
        out.append(Collective(
            kind=m.group(3), dtype=m.group(1), dims=dims,
            groups=None if groups is None else
            tuple(tuple(g) for g in groups),
            line=line[:200]))
    return out


def input_output_aliases(hlo: str) -> List[Tuple[int, int]]:
    """The compiled module's donation result: ``(output_index,
    parameter_index)`` pairs from the ``input_output_alias={...}`` header
    (empty list = nothing aliased, every donated buffer was dropped)."""
    start = hlo.find("input_output_alias={")
    if start < 0:
        return []
    # the map nests braces ({ {0}: (0, {}, may-alias), ... }): scan to
    # the matching close instead of regexing over nesting
    i = hlo.index("{", start)
    depth = 0
    for j in range(i, len(hlo)):
        if hlo[j] == "{":
            depth += 1
        elif hlo[j] == "}":
            depth -= 1
            if depth == 0:
                break
    body = hlo[i:j + 1]
    return [(int(o), int(p)) for o, p in
            re.findall(r"\{(\d+)\}:\s*\((\d+),", body)]
