"""HLO-text analysis: collective bytes with while-loop trip-count scaling.

cost_analysis() has no collective statistics, so we parse the
post-partitioning HLO (compiled.as_text()): sum the result-shape bytes of
every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute instruction. Collectives inside scan-generated `while`
bodies execute trip-count times but appear once in the text, so we build
the computation call graph (while/call/conditional), extract each loop's
trip count from the comparison constant in its condition computation, and
scale bottom-up.

Byte convention: result-shape bytes of the collective (for all-gather this
is the gathered size — an upper bound on per-chip wire bytes; for
all-reduce it equals the tensor size, a lower bound on the 2x ring
traffic). A consumer turning bytes into wire time applies the
per-algorithm wire factors on top (a ring all-reduce moves about twice
its payload).
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(text: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def split_computations(hlo: str) -> Tuple[Dict[str, List[str]], str]:
    """Returns (computation name -> instruction lines, entry name)."""
    comps: Dict[str, List[str]] = {}
    cur = None
    entry = ""
    for line in hlo.splitlines():
        stripped = line.strip()
        if not line.startswith(" ") and stripped.endswith("{") \
                and (stripped.startswith("%") or stripped.startswith("ENTRY")):
            tok = stripped
            is_entry = tok.startswith("ENTRY")
            if is_entry:
                tok = tok[len("ENTRY"):].strip()
            name = tok.split(" ")[0].split("(")[0].lstrip("%")
            cur = name
            comps[cur] = []
            if is_entry:
                entry = name
            continue
        if stripped == "}":
            cur = None
            continue
        if cur is not None and stripped:
            comps[cur].append(stripped)
    return comps, entry


def _result_bytes(line: str, op: str) -> int:
    """Bytes of the instruction's result shape(s) (text before the op name)."""
    idx = line.find(f" {op}(")
    if idx < 0:
        idx = line.find(f" {op}-start(")
    head = line[:idx] if idx >= 0 else line.split("(")[0]
    eq = head.find("=")
    return _shape_bytes(head[eq + 1:] if eq >= 0 else head)


def _trip_count(cond_lines: List[str]) -> int:
    """Extract the loop bound from a scan condition computation."""
    const = 0
    for line in cond_lines:
        if "constant(" in line and ("s32" in line or "u32" in line):
            m = re.search(r"constant\((\d+)\)", line)
            if m:
                const = max(const, int(m.group(1)))
    return max(const, 1)


def collective_bytes(hlo: str) -> Dict[str, float]:
    """Trip-count-scaled collective bytes by kind, plus 'total'."""
    comps, entry = split_computations(hlo)

    # per-computation local collective bytes + sub-calls
    local: Dict[str, Dict[str, float]] = {}
    calls: Dict[str, List[Tuple[str, int]]] = defaultdict(list)
    while_re = re.compile(
        r"\bwhile\(.*condition=%?([\w\.\-]+), body=%?([\w\.\-]+)")
    call_re = re.compile(r"(?:\bcalls=|to_apply=)%?([\w\.\-]+)")

    for name, lines in comps.items():
        bucket: Dict[str, float] = defaultdict(float)
        for line in lines:
            if "-done" in line:        # async pair: count the -start only
                continue
            matched_coll = False
            for op in _COLLECTIVES:
                if re.search(rf"\b{op}(-start)?\(", line):
                    bucket[op] += _result_bytes(line, op)
                    matched_coll = True
                    break
            if matched_coll:
                continue
            m = while_re.search(line)
            if m:
                cond, body = m.group(1), m.group(2)
                trips = _trip_count(comps.get(cond, []))
                calls[name].append((body, trips))
                calls[name].append((cond, trips))
            else:
                for cm in call_re.finditer(line):
                    if cm.group(1) in comps:
                        calls[name].append((cm.group(1), 1))
        local[name] = dict(bucket)

    memo: Dict[str, Dict[str, float]] = {}

    def total_of(name: str, stack=()) -> Dict[str, float]:
        if name in memo:
            return memo[name]
        if name in stack:
            return {}
        out: Dict[str, float] = defaultdict(float)
        for k, v in local.get(name, {}).items():
            out[k] += v
        for child, mult in calls.get(name, []):
            sub = total_of(child, stack + (name,))
            for k, v in sub.items():
                out[k] += v * mult
        memo[name] = dict(out)
        return memo[name]

    if not entry:
        entry = max(comps, key=lambda c: len(comps[c])) if comps else ""
    result = {k: float(v) for k, v in total_of(entry).items()}
    result["total"] = float(sum(result.values()))
    return result


def collective_bytes_unscaled(hlo: str) -> Dict[str, float]:
    """Flat text scan (no trip scaling) — the naive lower bound."""
    bucket: Dict[str, float] = defaultdict(float)
    for line in hlo.splitlines():
        line = line.strip()
        if "-done" in line:
            continue
        for op in _COLLECTIVES:
            if re.search(rf"\b{op}(-start)?\(", line):
                bucket[op] += _result_bytes(line, op)
                break
    out = {k: float(v) for k, v in bucket.items()}
    out["total"] = float(sum(out.values()))
    return out
