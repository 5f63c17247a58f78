"""Extract roofline terms from compiled XLA artifacts.

``cost_analysis`` gives HLO FLOPs and bytes accessed; collective bytes are
NOT in cost_analysis, so we parse the post-SPMD optimized HLO text and sum
output-shape bytes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute op.

Collectives inside ``while`` loops (the layer-unit scan!) execute
trip-count-many times but appear once in the text, so the parser walks the
computation call graph and multiplies by XLA's ``known_trip_count``.

Hardware model: the published peaks of the chip, looked up by
``jax.Device.device_kind`` in ``PEAKS``.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

# Published per-chip peaks keyed by ``jax.Device.device_kind``.  A device
# that is not listed is an error, not a default: add it with its source.
PEAKS: Dict[str, Dict[str, object]] = {
    "TPU v5 lite": {
        "source": 'Google Cloud documentation, "TPU v5e"',
        "flops_bf16": 197e12,       # FLOP/s
        "ops_int8": 393e12,         # OP/s
        "hbm_bytes_s": 819e9,       # bytes/s, 16 GB HBM
        # 1,600 Gbit/s of chip-to-chip interconnect over 4 links
        "ici_link_bytes_s": 50e9,
    },
}


def peaks(device_kind: str) -> Dict[str, object]:
    """The ``PEAKS`` entry for a device kind; raises for one not listed."""
    if device_kind not in PEAKS:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        )
    return PEAKS[device_kind]

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|([\w\[\],\{\}]+))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\("
)
_WHILE_RE = re.compile(r"while\(.*?body=%?([\w\.\-]+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALL_RE = re.compile(r"\scall\(.*?to_apply=%?([\w\.\-]+)")
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{\s*$")


def _shape_bytes(text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclass
class CollectiveStats:
    bytes_by_kind: Dict[str, int] = field(default_factory=dict)
    count_by_kind: Dict[str, int] = field(default_factory=dict)

    @property
    def total_bytes(self) -> int:
        return sum(self.bytes_by_kind.values())

    def add(self, kind: str, nbytes: int, mult: int = 1):
        self.bytes_by_kind[kind] = self.bytes_by_kind.get(kind, 0) + nbytes * mult
        self.count_by_kind[kind] = self.count_by_kind.get(kind, 0) + mult


def _split_computations(hlo_text: str) -> Dict[str, List[str]]:
    comps: Dict[str, List[str]] = {}
    cur = None
    entry = None
    for line in hlo_text.splitlines():
        m = _COMP_HDR.match(line.strip())
        if m and ("->" in line):
            cur = m.group(1)
            comps[cur] = []
            if line.strip().startswith("ENTRY"):
                entry = cur
            continue
        if cur is not None:
            if line.strip() == "}":
                cur = None
            else:
                comps[cur].append(line)
    if entry is not None:
        comps["__entry__"] = comps[entry]
    return comps


def collective_stats(hlo_text: str) -> CollectiveStats:
    comps = _split_computations(hlo_text)
    entry_lines = comps.get("__entry__")
    if entry_lines is None:  # fallback: flat scan, no loop multipliers
        stats = CollectiveStats()
        for m in _COLL_RE.finditer(hlo_text):
            tshapes, sshape, kind, suffix = m.groups()
            if suffix == "-done":
                continue
            stats.add(kind, _shape_bytes(tshapes or sshape or ""))
        return stats

    stats = CollectiveStats()
    seen_stack: List[str] = []

    def visit(lines: List[str], mult: int):
        for line in lines:
            cm = _COLL_RE.search(line)
            if cm:
                tshapes, sshape, kind, suffix = cm.groups()
                if suffix != "-done":
                    stats.add(kind, _shape_bytes(tshapes or sshape or ""), mult)
                continue
            wm = _WHILE_RE.search(line)
            if wm:
                body = wm.group(1)
                tm = _TRIP_RE.search(line)
                trips = int(tm.group(1)) if tm else 1
                if body in comps and body not in seen_stack:
                    seen_stack.append(body)
                    visit(comps[body], mult * trips)
                    seen_stack.pop()
                continue
            km = _CALL_RE.search(line)
            if km and km.group(1) in comps and km.group(1) not in seen_stack:
                seen_stack.append(km.group(1))
                visit(comps[km.group(1)], mult)
                seen_stack.pop()

    visit(entry_lines, 1)
    return stats


_DEF_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(\w+)\[([\d,]*)\]"
)
_DOT_LINE_RE = re.compile(
    r"\sdot\(([^)]*)\).*?lhs_contracting_dims=\{([\d,]*)\}"
)
_CONV_LINE_RE = re.compile(r"\sconvolution\(([^)]*)\)")
_OPERANDS_RE = re.compile(r"%([\w\.\-]+)")


def _symbol_table(lines: List[str]) -> Dict[str, Tuple[str, List[int]]]:
    table: Dict[str, Tuple[str, List[int]]] = {}
    for line in lines:
        m = _DEF_RE.match(line)
        if m:
            name, dt, dims = m.groups()
            table[name] = (
                dt, [int(d) for d in dims.split(",")] if dims else []
            )
    return table


def _nbytes(dt: str, dims: List[int]) -> int:
    n = 1
    for d in dims:
        n *= d
    return n * _DTYPE_BYTES.get(dt, 0)


def hlo_compute_stats(hlo_text: str) -> Dict[str, int]:
    """Trip-count-aware dot(+conv) FLOPs and matmul HBM bytes.

    XLA's ``compiled.cost_analysis()`` does NOT multiply while-loop bodies by
    their trip count (observed: 4.8 TF for a program whose layer scan alone
    is ~25 TF), so we count matmul FLOPs and operand/output bytes ourselves,
    walking the call graph the same way collective_stats does.  Elementwise
    FLOPs are ignored (matmuls dominate >10x); bytes are a matmul-traffic
    lower bound on HBM traffic (fusions stream everything else through the
    same tiles)."""
    comps = _split_computations(hlo_text)
    entry = comps.get("__entry__")
    tables = {name: _symbol_table(lines) for name, lines in comps.items()}

    def line_stats(line: str, table) -> Tuple[int, int]:
        md = _DEF_RE.match(line)
        out_dt, out_dims = (md.group(2),
                            [int(d) for d in md.group(3).split(",")] if md and md.group(3) else []) \
            if md else (None, [])
        if " dot(" in line:
            m = _DOT_LINE_RE.search(line)
            if not (m and md):
                return 0, 0
            operands = _OPERANDS_RE.findall(m.group(1))
            if not operands or operands[0] not in table:
                return 0, 0
            lhs_dt, lhs_dims = table[operands[0]]
            csize = 1
            if m.group(2):
                for ci in m.group(2).split(","):
                    idx = int(ci)
                    if idx < len(lhs_dims):
                        csize *= lhs_dims[idx]
            out_size = 1
            for d in out_dims:
                out_size *= d
            flops = 2 * out_size * csize
            nbytes = _nbytes(out_dt, out_dims)
            for op in operands[:2]:
                if op in table:
                    nbytes += _nbytes(*table[op])
            return flops, nbytes
        if " convolution(" in line and md:
            m = _CONV_LINE_RE.search(line)
            if not m:
                return 0, 0
            operands = _OPERANDS_RE.findall(m.group(1))
            out_size = 1
            for d in out_dims:
                out_size *= d
            k_size = 1
            if len(operands) > 1 and operands[1] in table:
                _, k_dims = table[operands[1]]
                for d in k_dims[:-1]:
                    k_size *= d
            nbytes = _nbytes(out_dt, out_dims)
            for op in operands[:2]:
                if op in table:
                    nbytes += _nbytes(*table[op])
            return 2 * out_size * k_size, nbytes
        return 0, 0

    if entry is None:
        table = _symbol_table(hlo_text.splitlines())
        f = b = 0
        for l in hlo_text.splitlines():
            lf, lb = line_stats(l, table)
            f += lf
            b += lb
        return {"dot_flops": f, "dot_bytes": b}

    seen: List[str] = []

    def visit(comp_name: str, mult: int):
        lines = comps[comp_name]
        table = tables[comp_name]
        f = b = 0
        for line in lines:
            lf, lb = line_stats(line, table)
            if lf:
                f += lf * mult
                b += lb * mult
                continue
            wm = _WHILE_RE.search(line)
            if wm:
                body = wm.group(1)
                tm = _TRIP_RE.search(line)
                trips = int(tm.group(1)) if tm else 1
                if body in comps and body not in seen:
                    seen.append(body)
                    sf, sb = visit(body, mult * trips)
                    f += sf
                    b += sb
                    seen.pop()
                continue
            km = _CALL_RE.search(line)
            if km and km.group(1) in comps and km.group(1) not in seen:
                seen.append(km.group(1))
                sf, sb = visit(km.group(1), mult)
                f += sf
                b += sb
                seen.pop()
                continue
            fm = re.search(r"fusion\(.*?calls=%?([\w\.\-]+)", line)
            if fm and fm.group(1) in comps and fm.group(1) not in seen:
                seen.append(fm.group(1))
                sf, sb = visit(fm.group(1), mult)
                f += sf
                b += sb
                seen.pop()
        return f, b

    f, b = visit("__entry__", 1)
    return {"dot_flops": f, "dot_bytes": b}


def decode_per_token_stats(hlo_text: str, batch: int) -> Dict[str, float]:
    """Modeled cost of ONE decoded token from a decode-step program.

    A decode step advances every sequence in the batch by exactly one token,
    so per-token cost is the program total divided by the batch — the
    serving analogue of the round kernels' modeled-bytes rows.  Feeding a
    prefill/train program in gives per-*step-row* numbers, which is not the
    same thing; only use decode-step HLO here."""
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    comp = hlo_compute_stats(hlo_text)
    coll = collective_stats(hlo_text)
    return {
        "dot_flops_per_token": comp["dot_flops"] / batch,
        "dot_bytes_per_token": comp["dot_bytes"] / batch,
        "collective_bytes_per_token": coll.total_bytes / batch,
    }


def roofline_terms(
    *,
    flops: float,
    bytes_accessed: float,
    collective_bytes: float,
    chips: int,
    device_kind: str,
) -> Dict[str, float]:
    """The three §Roofline terms, in seconds (per step, whole mesh).

    flops / bytes_accessed are cost_analysis() *per-device* numbers times
    `chips` when aggregated by the caller; here we take WHOLE-PROGRAM totals
    and divide by the mesh."""
    pk = peaks(device_kind)
    compute_s = flops / (chips * pk["flops_bf16"])
    memory_s = bytes_accessed / (chips * pk["hbm_bytes_s"])
    collective_s = collective_bytes / (chips * pk["ici_link_bytes_s"])
    dominant = max(
        ("compute", compute_s), ("memory", memory_s),
        ("collective", collective_s), key=lambda kv: kv[1],
    )[0]
    return {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": collective_s,
        "dominant": dominant,
    }
