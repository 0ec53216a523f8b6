"""Collective-byte accounting from compiled (post-SPMD-partitioning) HLO.

``cost_analysis`` does not expose collective traffic, so we parse the
per-device HLO text. Operand types are %refs in HLO text, so we read each
collective's **result** shape (inline on the defining line) plus its
``replica_groups`` size, and convert to per-device **wire bytes** with ring
factors:

    all-gather         result · (g-1)/g        (result = gathered tensor)
    reduce-scatter     result · (g-1)          (result = scattered shard)
    all-reduce         result · 2(g-1)/g
    all-to-all         result · (g-1)/g
    collective-permute result                  (point-to-point)

Shapes in the partitioned module are per-device, so totals are per-device
bytes over the busiest link under a ring schedule — the roofline layer
divides by per-link bandwidth directly.
"""
from __future__ import annotations

import re
from collections import defaultdict

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

# defining line: "%name = <result-type> <kind>[-start|-done](..."
_LINE_RE = re.compile(
    r"=\s+(\([^=]*?\)|\S+?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")

_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]+|pred)\[([0-9,]*)\]")

# replica_groups=[n_groups,group_size]<=...   (iota form)
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
# replica_groups={{0,1,2},{...}}              (explicit form)
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9,]*)\}")

_WIRE = {
    "all-gather": lambda b, g: b * (g - 1) / g,
    "reduce-scatter": lambda b, g: b * (g - 1),
    "all-reduce": lambda b, g: b * 2 * (g - 1) / g,
    "all-to-all": lambda b, g: b * (g - 1) / g,
    "collective-permute": lambda b, g: float(b),
}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return max(int(m.group(2)), 1)
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return max(len([x for x in m.group(1).split(",") if x != ""]), 1)
    return 2  # collective-permute / unknown: factor cancels anyway


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Per-device wire bytes + op counts per collective kind.

    Returns {kind: bytes, ..., "total": bytes, "n_<kind>": count}.
    Async pairs are counted at -start (last tuple element = output buffer);
    -done lines are skipped.
    """
    out: dict[str, float] = defaultdict(float)
    counts: dict[str, int] = defaultdict(int)
    for line in hlo_text.splitlines():
        m = _LINE_RE.search(line)
        if not m:
            continue
        result_type, kind, suffix = m.group(1), m.group(2), m.group(3)
        if suffix == "-done":
            continue
        shapes = _SHAPE_RE.findall(result_type)
        if not shapes:
            continue
        if suffix == "-start":
            shapes = shapes[-1:]          # (operand, result) tuple: output
        b = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        g = _group_size(line)
        out[kind] += _WIRE[kind](b, g)
        out[f"{kind}_result_bytes"] += b
        counts[kind] += 1
    rec = {k: v for k, v in out.items()}
    rec["total"] = sum(v for k, v in out.items()
                       if not k.endswith("_result_bytes"))
    for k, c in counts.items():
        rec[f"n_{k}"] = c
    return rec


# instruction line: "[ROOT ]%name = <type> <op>(...), ..., metadata={...}"
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME_RE = re.compile(r'\bop_name="([^"]*)"')


def op_scopes(hlo_text: str) -> dict[str, str]:
    """``{instruction name: scope path}`` of every instruction in compiled
    HLO text. The path is the instruction's ``metadata={op_name="..."}``,
    e.g. ``jit(mr_segment)/local_reduce/jit(sort)/sort``: the jitted
    function, then each ``jax.named_scope`` and primitive. An instruction
    the compiler made up, with no ``op_name``, maps to ``""``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if m:
            n = _OP_NAME_RE.search(line)
            out[m.group(1)] = n.group(1) if n else ""
    return out
