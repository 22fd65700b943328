"""Loop-aware post-SPMD HLO text analysis.

XLA's ``cost_analysis()`` counts while-loop bodies once and its CPU
bytes-accessed model ignores fusion boundaries.  This parser rebuilds both
metrics from the compiled HLO text:

* **Loop multipliers** — jax scans lower to ``while`` ops annotated with
  ``backend_config={"known_trip_count":{"n":...}}``; every computation
  reachable as a while body/condition inherits ``parent × trip``.
* **Collective bytes** — output-shape bytes of all-gather / all-reduce /
  reduce-scatter / all-to-all / collective-permute, × loop multiplier,
  × per-kind ring-traffic factor.
* **HBM traffic** — Σ over instructions of (operands + output) bytes,
  with fusions counted at their boundary (internal ops live in
  registers/VMEM — the TPU model), dynamic-update-slice counted at the
  update size (in-place on TPU), and layout/metadata ops skipped.

Per-device numbers (post-SPMD shapes are per-device).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any

_DTYPE_BYTES = {
    "pred": 1, "s2": 1, "u2": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "token": 0, "opaque": 0,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_HEADER_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\((.*)\)\s*->\s*.*\{\s*$")
_INSTR_RE = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')

_SKIP_OPS = {
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "after-all", "iota", "while", "conditional", "call", "partition-id",
    "replica-id", "rng-bit-generator",
}

COLLECTIVE_KINDS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

TRAFFIC_MULTIPLIER = {
    "all-gather": 1.0,
    "all-reduce": 2.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


@dataclasses.dataclass
class Instr:
    name: str
    shape: str
    opcode: str
    operands: list[str]
    attrs: str

    @property
    def out_bytes(self) -> int:
        return shape_bytes(self.shape)


def _split_rhs(rhs: str) -> tuple[str, str, str, str] | None:
    """rhs = '<shape> <opcode>(<operands>)<attrs>' -> parts."""
    rhs = rhs.strip()
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                shape, rest = rhs[: i + 1], rhs[i + 1 :]
                break
        else:
            return None
    else:
        sp = rhs.find(" ")
        if sp < 0:
            return None
        shape, rest = rhs[:sp], rhs[sp:]
    rest = rest.strip()
    m = re.match(r"([\w\-]+)\(", rest)
    if not m:
        return None
    opcode = m.group(1)
    depth, start = 0, rest.find("(")
    for i in range(start, len(rest)):
        depth += rest[i] == "("
        depth -= rest[i] == ")"
        if depth == 0:
            operands_str = rest[start + 1 : i]
            attrs = rest[i + 1 :]
            break
    else:
        return None
    return shape, opcode, operands_str, attrs


def parse_module(text: str):
    """Returns (computations: {name: [Instr]}, entry_name)."""
    comps: dict[str, list[Instr]] = {}
    entry = None
    cur: list[Instr] | None = None
    for line in text.splitlines():
        header = _COMP_HEADER_RE.match(line)
        if header:
            name = header.group(2)
            comps[name] = []
            cur = comps[name]
            if header.group(1):
                entry = name
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        parts = _split_rhs(m.group(2))
        if parts is None:
            continue
        shape, opcode, operands_str, attrs = parts
        operands = re.findall(r"%([\w.\-]+)", operands_str)
        cur.append(Instr(m.group(1), shape, opcode, operands, attrs))
    return comps, entry


def loop_multipliers(comps, entry) -> dict[str, float]:
    """Computation name -> product of enclosing while trip counts."""
    mult = {entry: 1.0}
    # whiles: (parent, body, cond, trip)
    edges = []
    for comp_name, instrs in comps.items():
        for ins in instrs:
            if ins.opcode != "while":
                continue
            body = re.search(r"body=%?([\w.\-]+)", ins.attrs)
            cond = re.search(r"condition=%?([\w.\-]+)", ins.attrs)
            trip_m = _TRIP_RE.search(ins.attrs)
            trip = float(trip_m.group(1)) if trip_m else 1.0
            if body and cond:
                edges.append((comp_name, body.group(1), cond.group(1), trip))
    changed = True
    while changed:
        changed = False
        for parent, body, cond, trip in edges:
            if parent in mult:
                for child, m in ((body, mult[parent] * trip), (cond, mult[parent])):
                    if mult.get(child) != m:
                        mult[child] = m
                        changed = True
    return mult


def _instr_hbm_bytes(ins: Instr, name_bytes: dict[str, int]) -> int:
    if ins.opcode in _SKIP_OPS:
        return 0
    out = ins.out_bytes
    if ins.opcode == "dynamic-update-slice":
        # in-place on TPU: traffic = update read + write
        upd = name_bytes.get(ins.operands[1], 0) if len(ins.operands) > 1 else 0
        return 2 * upd
    if ins.opcode == "broadcast":
        return out  # read side is negligible
    ops = sum(name_bytes.get(o, 0) for o in ins.operands)
    return out + ops


def analyze_hlo(text: str) -> dict[str, Any]:
    """Loop-aware collective bytes + HBM traffic (per device)."""
    comps, entry = parse_module(text)
    mult = loop_multipliers(comps, entry)

    coll_bytes = {k: 0.0 for k in COLLECTIVE_KINDS}
    coll_counts = {k: 0 for k in COLLECTIVE_KINDS}
    coll_static = {k: 0 for k in COLLECTIVE_KINDS}
    top: list[tuple[float, str, str, float, str]] = []
    hbm = 0.0

    for comp_name, m in mult.items():
        instrs = comps.get(comp_name)
        if instrs is None:
            continue
        name_bytes = {i.name: i.out_bytes for i in instrs}
        for ins in instrs:
            base = ins.opcode
            if base.endswith("-start"):
                base = base[: -len("-start")]
            if base.endswith("-done"):
                continue
            if base in COLLECTIVE_KINDS:
                coll_bytes[base] += ins.out_bytes * m
                coll_counts[base] += int(m)
                coll_static[base] += 1
                opm = re.search(r'op_name="([^"]+)"', ins.attrs)
                top.append((
                    ins.out_bytes * m * TRAFFIC_MULTIPLIER[base],
                    base, ins.shape[:60], m,
                    (opm.group(1)[-120:] if opm else ""),
                ))
            hbm += _instr_hbm_bytes(ins, name_bytes) * m
    top.sort(reverse=True)

    weighted = sum(coll_bytes[k] * TRAFFIC_MULTIPLIER[k] for k in COLLECTIVE_KINDS)
    return {
        "collective_bytes_by_kind": coll_bytes,
        "collective_counts_dynamic": coll_counts,
        "collective_counts_static": coll_static,
        "collective_weighted_bytes": weighted,
        "hbm_traffic_bytes": hbm,
        "num_computations": len(comps),
        "num_loops": sum(1 for v in mult.values() if v > 1),
        "top_collectives": [
            {"gib": round(b / 2**30, 2), "kind": k, "shape": s,
             "mult": m, "op": o}
            for b, k, s, m, o in top[:12]
        ],
    }


# ---------------------------------------------------------------------------
# Conditional-region isolation (the emit-split HLO assertion)
# ---------------------------------------------------------------------------

# Computation-reference attributes and whether following them crosses
# into a conditional's branch (the "guarded" edges).  An SPMD program is
# one module for every device; what distinguishes "device d never runs
# the LM head" is that the head ops live only inside conditional branch
# computations whose predicate (a plan column) is false on device d.
_CALL_ATTRS = (
    ("to_apply=%?([\\w.\\-]+)", False),
    ("body=%?([\\w.\\-]+)", False),
    ("condition=%?([\\w.\\-]+)", False),
    # Fusions reference their body as calls=%fused_computation (the
    # textual form XLA emits); missing this edge would leave fusion
    # bodies unreachable and silently classify fused ops as guarded.
    ("calls=\\{([^}]*)\\}", False),
    ("calls=%?([\\w.\\-]+)", False),
    ("called_computations=\\{([^}]*)\\}", False),
    ("true_computation=%?([\\w.\\-]+)", True),
    ("false_computation=%?([\\w.\\-]+)", True),
    ("branch_computations=\\{([^}]*)\\}", True),
)


def _call_edges(instrs):
    """Yield (callee, guarded) for every computation reference."""
    for ins in instrs:
        for pattern, guarded in _CALL_ATTRS:
            for m in re.finditer(pattern, ins.attrs):
                for name in re.findall(r"%?([\w.\-]+)", m.group(1)):
                    yield name, guarded


def unguarded_matches(text: str, match) -> tuple[int, int]:
    """Count instructions satisfying ``match(Instr)`` in the module, and
    how many of those sit in a computation reachable from the entry
    *without* crossing into a conditional branch.

    Returns ``(total, unguarded)``.  ``unguarded == 0`` with ``total >
    0`` means every matching op is region-isolated behind a conditional
    — combined with a plan whose gating column is zero on a device, that
    device's executed tick body never contains the op.
    """
    comps, entry = parse_module(text)
    edges: dict[str, list[tuple[str, bool]]] = {}
    for name, instrs in comps.items():
        edges[name] = list(_call_edges(instrs))
    # BFS over non-guarded edges only.
    unguarded_comps: set[str] = set()
    frontier = [entry] if entry else []
    while frontier:
        name = frontier.pop()
        if name in unguarded_comps or name not in comps:
            continue
        unguarded_comps.add(name)
        for callee, guarded in edges.get(name, ()):
            if not guarded:
                frontier.append(callee)
    total = unguarded = 0
    for name, instrs in comps.items():
        for ins in instrs:
            if not match(ins):
                continue
            total += 1
            if name in unguarded_comps:
                unguarded += 1
    return total, unguarded


def slab_scatter_counts(text: str, slab_bytes: int) -> tuple[int, int]:
    """Count slab-sized cache writes: scatter / dynamic-update-slice ops
    whose *output* is at least ``slab_bytes`` (the full KV-cache slab for
    one layer group — a row write's output is the same slab shape, but a
    functional ``cache.at[idx, pos].set(rows)`` materializes the whole
    updated slab as a new buffer, which is what shows up here).

    Returns ``(total, unguarded)`` with the same guarded/unguarded split
    as :func:`unguarded_matches`: an op inside a conditional branch does
    not run on devices where the branch predicate is false.  The fused
    Pallas decode-attention path performs the row substitution inside
    the kernel, so its steady tick carries strictly fewer slab-sized
    scatters than the XLA path — asserted comparatively (pallas < xla)
    rather than as an absolute zero, because the in-plan admission
    buffer legitimately writes freshly prefilled rows.
    """

    def is_slab_write(ins) -> bool:
        if ins.opcode not in ("scatter", "dynamic-update-slice"):
            return False
        return ins.out_bytes >= slab_bytes

    return unguarded_matches(text, is_slab_write)


def fused_region_present(text: str, marker: str) -> bool:
    """True iff any instruction's ``op_name`` metadata contains
    ``marker``.  The Pallas ops wrap their ``pallas_call`` in
    ``jax.named_scope(FUSION_SCOPE)``; the scope name survives into the
    compiled module's op_name metadata, so presence of the marker means
    the fused kernel (or, in interpret mode, its lowered emulation) is
    structurally in the executed program — and absence in an XLA-mode
    module is the negative control.
    """
    for m in re.finditer(r'op_name="([^"]*)"', text):
        if marker in m.group(1):
            return True
    return False


def tpu_kernel_present(text: str, marker: str) -> bool:
    """True iff a compiled Mosaic kernel call (``tpu_custom_call``)
    carries ``marker`` in its ``op_name`` metadata: the fused kernel
    itself runs on the TPU, not an XLA emulation of it."""
    return any(
        'custom_call_target="tpu_custom_call"' in line and marker in line
        for line in text.splitlines()
    )


def head_matmul_conditional_only(text: str, logits_width: int) -> bool:
    """True iff the module contains at least one logits-width matmul and
    every one of them is conditional-guarded (see
    :func:`unguarded_matches`).  The serving emit-split acceptance
    check: with the plan's ``emit`` column nonzero only on the final
    pipeline device, a guarded head matmul is structurally absent from
    every other device's executed tick body."""

    def is_head_dot(ins) -> bool:
        if ins.opcode not in ("dot", "custom-call"):
            return False
        if ins.opcode == "custom-call" and "matmul" not in ins.attrs.lower():
            return False
        dims = [
            int(d)
            for _, ds in _SHAPE_RE.findall(ins.shape)
            if ds
            for d in ds.split(",")
        ]
        return logits_width in dims

    total, unguarded = unguarded_matches(text, is_head_dot)
    return total > 0 and unguarded == 0
