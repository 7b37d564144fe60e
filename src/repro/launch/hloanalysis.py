"""Trip-count-aware analysis of post-SPMD optimized HLO text.

XLA's ``compiled.cost_analysis()`` counts a while-loop (lax.scan) body ONCE,
so for scan-over-layers models both FLOPs and collective bytes are
undercounted by ~n_layers. This module parses the HLO text, resolves each
computation's execution multiplier (product of enclosing while trip counts,
taken from the loop's ``known_trip_count`` backend config) and reports:

  * collective bytes by type, weighted by multiplier
  * dot FLOPs, weighted  (the remat/redundancy-aware "HLO_FLOPs")
"""
from __future__ import annotations

import re
from collections import defaultdict

_DTYPE_BYTES = {"pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2,
                "u16": 2, "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4,
                "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16}

_COMP_RE = re.compile(r"^(ENTRY )?%?([\w.\-]+) \(.*\)(?: -> .*)? \{\s*$")
_WHILE_RE = re.compile(
    r"\bwhile\(.*?\), condition=%?([\w.\-]+), body=%?([\w.\-]+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALL_RE = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_BRANCH_RE = re.compile(r"branch_computations=\{([^}]*)\}")
_SHAPE_RE = re.compile(
    r"(pred|bf16|f16|f32|f64|s4|u4|s8|u8|s16|u16|s32|u32|s64|u64|c64|c128)"
    r"\[([\d,]*)\]")
_ASSIGN_RE = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = "
    r"((?:pred|bf16|f16|f32|f64|s4|u4|s8|u8|s16|u16|s32|u32|s64|u64|c64|c128)"
    r"\[[\d,]*\])")
_COLL_RE = re.compile(
    r"=\s*(\([^=]*?\)|[\w\[\],{}]+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_GROUP_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUP_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
# operands may be typed ("dot(f32[128,128]{1,0} %lhs, ...)") or bare
# ("dot(%lhs, ...)") depending on the XLA version
_DOT_RE = re.compile(
    r"=\s*[\w]+\[([\d,]*)\][^=]*?\bdot\("
    r"\s*(?:[\w]+\[[\d,]*\](?:\{[\d,]*\})?\s+)?%([\w.\-]+),")
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")


def _dims(s):
    return [int(d) for d in s.split(",") if d]


def _nbytes(dt, dims):
    n = 1
    for d in dims:
        n *= d
    return n * _DTYPE_BYTES.get(dt, 4)


def split_computations(text: str):
    """{name: [lines]}; also returns entry computation name."""
    comps, entry = {}, None
    cur, buf = None, []
    for line in text.splitlines():
        stripped = line.rstrip()
        m = _COMP_RE.match(stripped)
        if m:
            cur = m.group(2)
            if m.group(1):
                entry = cur
            buf = []
            comps[cur] = buf
        elif stripped == "}":
            cur = None
        elif cur is not None:
            buf.append(stripped)
    if entry is None and comps:
        entry = list(comps)[-1]
    return comps, entry


def computation_multipliers(text: str):
    """{computation_name: times executed} via DFS from the entry."""
    comps, entry = split_computations(text)
    mult = defaultdict(float)

    def visit(name, m):
        if name not in comps or m == 0:
            return
        mult[name] += m
        for ln in comps[name]:
            wm = _WHILE_RE.search(ln)
            if wm:
                cond, body = wm.group(1), wm.group(2)
                tm = _TRIP_RE.search(ln)
                trips = int(tm.group(1)) if tm else 1
                visit(cond, m * (trips + 1))
                visit(body, m * trips)
                continue
            bm = _BRANCH_RE.search(ln)
            if bm:
                for callee in re.findall(r"[\w.\-]+", bm.group(1)):
                    visit(callee, m)
                continue
            for cm in _CALL_RE.finditer(ln):
                visit(cm.group(1), m)

    visit(entry, 1.0)
    return comps, dict(mult)


def _group_size(ln):
    g = _GROUP_RE.search(ln)
    if g:
        return max(int(g.group(2)), 1)
    g = _GROUP_LIST_RE.search(ln)
    if g:
        return max(len(g.group(1).split(",")), 1)
    return 2


def _moved_bytes(kind, result_bytes, n):
    """Ring-algorithm bytes actually moved per device, from result bytes."""
    f = (n - 1) / n
    if kind == "all-reduce":
        return 2.0 * result_bytes * f
    if kind == "all-gather":
        return result_bytes * f
    if kind == "reduce-scatter":
        return result_bytes * (n - 1)
    if kind == "all-to-all":
        return result_bytes * f
    return result_bytes          # collective-permute


def weighted_collectives(text: str):
    comps, mult = computation_multipliers(text)
    out = defaultdict(float)
    for name, lines in comps.items():
        m = mult.get(name, 0)
        if not m:
            continue
        for ln in lines:
            cm = _COLL_RE.search(ln)
            if not cm:
                continue
            kind = cm.group(2)
            nbytes = sum(_nbytes(dt, _dims(dims))
                         for dt, dims in _SHAPE_RE.findall(cm.group(1)))
            out[kind] += nbytes * m
            out[kind + "_count"] += m
            out["moved_bytes"] += _moved_bytes(kind, nbytes,
                                               _group_size(ln)) * m
    return dict(out)


def weighted_dot_flops(text: str):
    comps, mult = computation_multipliers(text)
    total = 0.0
    for name, lines in comps.items():
        m = mult.get(name, 0)
        if not m:
            continue
        # symbol table: var -> dims (array results only)
        sym = {}
        for ln in lines:
            am = _ASSIGN_RE.match(ln)
            if am:
                sm = _SHAPE_RE.search(am.group(2))
                if sm:
                    sym[am.group(1)] = _dims(sm.group(2))
        # parameters: "%p = (..) parameter(i)" handled above only for arrays;
        # tuple params feed get-tuple-element lines which carry shapes anyway.
        for ln in lines:
            dm = _DOT_RE.search(ln)
            if not dm:
                continue
            out_dims = _dims(dm.group(1))
            lhs = sym.get(dm.group(2))
            cm = _LHS_CONTRACT_RE.search(ln)
            contract = 1
            if lhs is not None and cm and cm.group(1):
                for c in _dims(cm.group(1)):
                    if c < len(lhs):
                        contract *= lhs[c]
            n_out = 1
            for d in out_dims:
                n_out *= d
            total += 2.0 * n_out * contract * m
    return total


def analyze(text: str):
    return {"collectives": weighted_collectives(text),
            "hlo_dot_flops": weighted_dot_flops(text)}


def cost_analysis_dict(compiled):
    """``compiled.cost_analysis()``, numeric entries only."""
    ca = compiled.cost_analysis()
    return {k: v for k, v in ca.items() if isinstance(v, (int, float))}


def compiled_costs(fn, *args):
    """Lower + compile ``fn`` on the current backend (args may be
    ShapeDtypeStructs — nothing is materialized or executed) and return
    {flops, bytes_accessed, hlo_dot_flops}: the backend's cost analysis
    with the trip-count-weighted dot FLOPs alongside. ``flops`` falls back
    to the HLO dot count when the backend reports none. Note convolutions
    lower to ``convolution(`` not ``dot(``, so for CNNs the backend count
    is the authoritative one."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    ca = cost_analysis_dict(compiled)
    flops = float(ca.get("flops", 0.0))
    byt = float(ca.get("bytes accessed", 0.0))
    hlo = weighted_dot_flops(compiled.as_text())
    if flops <= 0.0:
        flops = hlo
    return {"flops": flops, "bytes_accessed": byt, "hlo_dot_flops": hlo}
