"""Per-region cost attribution from HLO text (docs/OBSERVABILITY.md §Perf).

XLA's ``cost_analysis()`` prices a WHOLE program — one flops number, one
bytes number — which is how the repo got an MFU headline but no map of
where the 27.85 ms step goes.  The missing per-region view is
recoverable from the compiled module's own text: every HLO instruction
carries ``metadata={op_name="jit(step)/jit(main)/<scopes...>/<prim>"}``
where ``<scopes...>`` is the ``jax.named_scope`` / flax-module-path
stack (``utils/profiling.py`` annotates the loss stages; flax names the
trunk's blocks for free).  This module parses that text, prices each
instruction with an analytic cost model (the same flavor of estimate
``cost_analysis`` itself makes), and aggregates FLOPs / bytes-accessed /
collective bytes per region.

Honesty notes, also stamped into every report:

  * FLOPs are analytic (2MNK gemms, window*out convs, 1/elem
    elementwise) — the region SHARES are the product; absolute numbers
    reconcile against XLA's own total in the report (``coverage``).
  * bytes are operand+result sizes per instruction; instructions INSIDE
    a fusion contribute flops only, while the fusion call site
    contributes its operand/result bytes — i.e. bytes approximate
    post-fusion HBM traffic, not materialized intermediates.
  * ``while`` bodies (lax.scan) multiply by a best-effort trip count
    read off the loop condition; when that fails the body counts once
    and the region is flagged ``trip_count_unknown``.

Stdlib-only (text in, dicts out) — usable from jax-free processes.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

# Region key for ops outside any named scope / module path.
UNSCOPED = "(unscoped)"

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1,
    "f8e5m2": 1, "f8e4m3b11fnuz": 1, "s16": 2, "u16": 2, "f16": 2,
    "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "token": 0, "tuple": 0,
}

# Pure data movement / bookkeeping: no FLOPs (bytes still count).
_ZERO_FLOP_OPS = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "bitcast-convert", "broadcast", "reshape", "transpose", "copy",
    "copy-start", "copy-done", "slice", "dynamic-slice",
    "dynamic-update-slice", "concatenate", "pad", "gather", "scatter",
    "iota", "convert", "reverse", "after-all", "rng-bit-generator",
    "rng", "partition-id", "replica-id", "custom-call", "infeed",
    "outfeed", "send", "recv", "send-done", "recv-done", "domain",
    "opt-barrier", "add-dependency",
})

# Bookkeeping ops that contribute NOTHING (not even bytes): they have
# no runtime cost of their own.
_SKIP_OPS = frozenset({
    "parameter", "constant", "tuple", "get-tuple-element", "after-all",
    "domain", "opt-barrier", "add-dependency",
})

_COLLECTIVE_OPS = frozenset({
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "all-reduce-start",
    "all-gather-start", "collective-permute-start",
})

_INSTR_RE = re.compile(
    # The type charset includes parens: TPU-optimized HLO stamps tiled
    # layouts like f32[8,16]{1,0:T(8,128)(2,1)} on non-tuple results,
    # and a charset without ( ) fails to match every such instruction —
    # invisible on CPU (no tiling), empty region tables on the chip.
    # Tuple types match LAZILY up to the ` opcode(` anchor (not
    # ``[^=]*?``): XLA comments element indices past 4 as /*index=5*/,
    # and an =-excluding charset fails on every 6+-element tuple — so
    # a ``while`` with a large carry (the ring engine's scan) never
    # parsed and its whole body went unwalked.
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<type>\(.*?\)|[\w\[\]{},:#*\.()]+)\s+"
    r"(?P<opcode>[\w\-]+)\(",
)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")
_METADATA_RE = re.compile(r'metadata=\{[^{}]*?op_name="([^"]*)"')
_CALLED_RE = re.compile(
    r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_COMP_HEADER_RE = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*(?:\(.*)?\{\s*$")


@dataclasses.dataclass
class Instr:
    name: str
    opcode: str
    out_shapes: List[Tuple[str, Tuple[int, ...]]]
    operand_shapes: List[Tuple[str, Tuple[int, ...]]]
    operands_raw: str   # raw operand-list text (constant values live here)
    attrs: str          # raw text after the operand list
    op_name: str        # metadata op_name ("" when absent)
    called: List[str]   # computations referenced via calls/to_apply/...


def _shapes_in(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    out = []
    for dtype, dims in _SHAPE_RE.findall(text):
        if dtype not in _DTYPE_BYTES:
            # Layout/tiling artifacts like T(8,128) match the shape
            # regex; a real shape always leads with a known dtype.
            continue
        out.append(
            (dtype, tuple(int(d) for d in dims.split(",") if d != ""))
        )
    return out


def _elems(shape: Tuple[int, ...]) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def _shape_bytes(shapes: List[Tuple[str, Tuple[int, ...]]]) -> float:
    return float(sum(
        _elems(dims) * _DTYPE_BYTES.get(dtype, 4) for dtype, dims in shapes
    ))


def _operand_section(line: str, start: int) -> Tuple[str, int]:
    """The operand list between the opcode's parens; paren matching
    ignores parens nested in layout braces (``{1,0:T(8,128)}``)."""
    depth, brace, i = 0, 0, start
    for i in range(start, len(line)):
        c = line[i]
        if c == "{":
            brace += 1
        elif c == "}":
            brace -= 1
        elif brace == 0 and c == "(":
            depth += 1
        elif brace == 0 and c == ")":
            depth -= 1
            if depth == 0:
                return line[start + 1:i], i + 1
    return line[start + 1:], len(line)


def parse_hlo_computations(text: str) -> Tuple[str, Dict[str, List[Instr]]]:
    """HLO module text -> (entry_name, {computation: [Instr, ...]})."""
    comps: Dict[str, List[Instr]] = {}
    entry = ""
    current: Optional[str] = None
    # XLA prints operands by name only (``dot(%x.1, %x.1)``): their
    # shapes come from the defining instruction, looked up here.
    defined: Dict[str, List[Tuple[str, Tuple[int, ...]]]] = {}
    for line in text.splitlines():
        stripped = line.strip()
        if current is None:
            m = _COMP_HEADER_RE.match(stripped)
            if m and not stripped.startswith("HloModule"):
                current = m.group(2)
                comps[current] = []
                defined = {}
                if m.group(1):
                    entry = current
            continue
        if stripped == "}":
            current = None
            continue
        m = _INSTR_RE.match(line)
        if not m:
            continue
        opcode = m.group("opcode")
        operands, rest = _operand_section(line, line.find("(", m.end() - 1))
        attrs = line[rest:]
        meta = _METADATA_RE.search(attrs)
        out_shapes = _shapes_in(m.group("type"))
        defined[m.group("name")] = out_shapes
        operand_shapes = _shapes_in(operands)
        if not operand_shapes:
            operand_shapes = [
                s for ref in _OPERAND_NAME_RE.findall(operands)
                for s in defined.get(ref, [])]
        comps[current].append(Instr(
            name=m.group("name"),
            opcode=opcode,
            out_shapes=out_shapes,
            operand_shapes=operand_shapes,
            operands_raw=operands,
            attrs=attrs,
            op_name=meta.group(1) if meta else "",
            called=_CALLED_RE.findall(attrs),
        ))
    if not entry and comps:
        entry = next(iter(comps))
    return entry, comps


# -- op_name -> region --------------------------------------------------------

def _split_scopes(op_name: str) -> List[str]:
    """Split an op_name path on depth-0 slashes (scope names like
    ``npair/sim`` appear INSIDE ``jvp(...)`` wrappers, where the slash
    must not split the wrapper)."""
    parts, depth, cur = [], 0, []
    for c in op_name:
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        if c == "/" and depth == 0:
            parts.append("".join(cur))
            cur = []
        else:
            cur.append(c)
    if cur:
        parts.append("".join(cur))
    return parts


_WRAPPER_RE = re.compile(r"^(jit|jvp|vjp|transpose|vmap|pmap|remat|"
                         r"custom_jvp|custom_vjp|checkpoint)\((.*)\)$")


def _unwrap(segment: str) -> str:
    """Peel tracer wrappers: ``transpose(jvp(GoogLeNet))`` ->
    ``GoogLeNet`` (forward and backward of a scope attribute to the
    same region — the roofline doesn't care which direction moved the
    bytes)."""
    while True:
        m = _WRAPPER_RE.match(segment)
        if not m:
            return segment
        segment = m.group(2)


def region_of(op_name: str, depth: int = 2) -> str:
    """``jit(step)/jit(main)/jvp(npair/sim)/dot_general`` ->
    ``npair/sim``; the trailing primitive name drops, wrappers unwrap,
    ``jit(main)``/outer-jit segments and empty leftovers vanish, and
    the result truncates to ``depth`` path segments (0 = unlimited)."""
    raw = _split_scopes(op_name)
    if not raw:
        return UNSCOPED
    segs: List[str] = []
    # Control-flow structure segments (lax.scan/while/cond lowering)
    # carry no attribution information — without this filter every
    # scan body collapses into one "while/body" region and the REAL
    # scopes inside it vanish past the depth cut.
    structural = ("main", "while", "body", "cond", "branch",
                  "closed_call")
    for seg in raw[:-1]:  # the last segment is the primitive name
        seg = _unwrap(seg)
        if not seg or seg in structural or seg.startswith("_"):
            continue
        segs.extend(s for s in seg.split("/") if s)
    # The outermost segment is the jitted function's own name (step,
    # train_step, f) — every op shares it, so it carries no contrast.
    if len(segs) > 1:
        segs = segs[1:]
    elif segs and raw[0].startswith("jit("):
        segs = []
    if not segs:
        return UNSCOPED
    if depth and depth > 0:
        segs = segs[:depth]
    return "/".join(segs)


# -- per-instruction cost model ----------------------------------------------

def _dims_attr(attrs: str, key: str) -> List[int]:
    m = re.search(key + r"=\{([\d,]*)\}", attrs)
    if not m:
        return []
    return [int(d) for d in m.group(1).split(",") if d != ""]


def _instr_flops(instr: Instr) -> float:
    op = instr.opcode
    out_elems = sum(_elems(dims) for _, dims in instr.out_shapes)
    if op in _ZERO_FLOP_OPS:
        return 0.0
    if op == "dot":
        # 2 * output elems * contracted extent (batch dims are part of
        # the output, so this is the full 2MNK including batching).
        if not instr.operand_shapes:
            return 0.0
        lhs = instr.operand_shapes[0][1]
        contract = 1
        for d in _dims_attr(instr.attrs, "lhs_contracting_dims"):
            if d < len(lhs):
                contract *= lhs[d]
        return 2.0 * out_elems * contract
    if op == "convolution":
        # 2 * output elems * (kernel elems / output features): each
        # output element is a dot over spatial-window x input-features.
        if len(instr.operand_shapes) < 2:
            return 0.0
        kshape = instr.operand_shapes[1][1]
        kelems = _elems(kshape)
        m = re.search(r"dim_labels=\w+_(\w+)->", instr.attrs)
        out_feat = 1
        if m and "o" in m.group(1):
            o_idx = m.group(1).index("o")
            if o_idx < len(kshape):
                out_feat = kshape[o_idx]
        return 2.0 * out_elems * (kelems / max(out_feat, 1))
    if op in ("reduce", "reduce-precision"):
        return float(sum(
            _elems(dims) for _, dims in instr.operand_shapes[:1]))
    if op in ("reduce-window", "select-and-scatter"):
        m = re.search(r"size=([\dx]+)", instr.attrs)
        window = 1
        if m:
            for d in m.group(1).split("x"):
                window *= int(d)
        return float(out_elems * window)
    if op in ("sort", "top-k"):
        # O(n log n)-ish; count the comparisons linearly — sort cost is
        # dwarfed by gemms in every program this repo builds.
        return float(sum(_elems(dims) for _, dims in instr.operand_shapes))
    # Elementwise / everything else: one op per output element.
    return float(out_elems)


def _instr_bytes(instr: Instr) -> float:
    return _shape_bytes(instr.operand_shapes) + _shape_bytes(
        instr.out_shapes)


_KNOWN_TRIP_RE = re.compile(r'"known_trip_count":\s*\{"n":"(\d+)"\}')
_CONDITION_RE = re.compile(r"condition=%?([\w.\-]+)")


def _while_trip_count(
    instr: Instr, comps: Dict[str, List[Instr]]
) -> Optional[int]:
    """Trip count of a ``while`` op: XLA's own
    ``backend_config={"known_trip_count":{"n":...}}`` when present,
    else the condition-compare heuristic.  The condition computation is
    found by its ``condition=`` attribute, NOT by position — HLO prints
    ``condition=`` before ``body=``, so ``called[0]`` is the condition
    (assuming body-first silently killed every trip count and scan
    regions undercounted by the trip factor)."""
    m = _KNOWN_TRIP_RE.search(instr.attrs)
    if m:
        n = int(m.group(1))
        return n if n > 0 else None
    m = _CONDITION_RE.search(instr.attrs)
    cond = comps.get(m.group(1), []) if m else []
    return _trip_count(cond)


def _trip_count(cond: List[Instr]) -> Optional[int]:
    """Best-effort lax.scan/while trip count off the loop condition:
    a ``compare(iv, constant(N)), direction=LT`` pattern."""
    consts = {}
    for ins in cond:
        if ins.opcode == "constant":
            m = re.fullmatch(r"\s*(-?\d+)\s*", ins.operands_raw)
            if m:
                consts[ins.name] = int(m.group(1))
    for ins in cond:
        if ins.opcode == "compare" and "direction=LT" in ins.attrs:
            if consts:
                n = max(consts.values())
                return n if n > 0 else None
    return None


# -- aggregation --------------------------------------------------------------

def attribute_regions(
    hlo_text: str, depth: int = 2
) -> Dict[str, Dict[str, float]]:
    """HLO module text -> ``{region: {"flops", "bytes",
    "collective_bytes", "ops"}}`` plus a ``"_notes"`` key listing
    attribution caveats hit (unknown trip counts etc.)."""
    entry, comps = parse_hlo_computations(hlo_text)
    regions: Dict[str, Dict[str, float]] = {}
    notes: List[str] = []
    unknown_trips: Dict[str, int] = {}

    def bucket(region: str) -> Dict[str, float]:
        return regions.setdefault(region, {
            "flops": 0.0, "bytes": 0.0, "collective_bytes": 0.0,
            "ops": 0.0,
        })

    def walk(comp_name: str, mult: float, count_bytes: bool,
             seen: Tuple[str, ...]) -> None:
        if comp_name not in comps or comp_name in seen:
            return
        for instr in comps[comp_name]:
            if instr.opcode in _SKIP_OPS:
                continue
            region = region_of(instr.op_name, depth)
            if instr.opcode == "fusion":
                # The fusion call site IS the memory traffic (operands
                # + result); the fused instructions carry the flops.
                if count_bytes:
                    bucket(region)["bytes"] += _instr_bytes(instr) * mult
                for callee in instr.called:
                    walk(callee, mult, False, seen + (comp_name,))
                continue
            if instr.opcode == "call":
                for callee in instr.called:
                    walk(callee, mult, count_bytes, seen + (comp_name,))
                continue
            if instr.opcode == "while":
                trip = _while_trip_count(instr, comps)
                if trip is None:
                    trip = 1
                    unknown_trips.setdefault(region, 0)
                    unknown_trips[region] += 1
                for callee in instr.called:
                    walk(callee, mult * trip, count_bytes,
                         seen + (comp_name,))
                continue
            if instr.opcode == "conditional":
                for callee in instr.called:
                    walk(callee, mult, count_bytes, seen + (comp_name,))
                continue
            b = bucket(region)
            b["ops"] += mult
            b["flops"] += _instr_flops(instr) * mult
            if count_bytes:
                b["bytes"] += _instr_bytes(instr) * mult
            if instr.opcode in _COLLECTIVE_OPS:
                b["collective_bytes"] += _shape_bytes(
                    instr.out_shapes) * mult

    walk(entry, 1.0, True, ())
    if unknown_trips:
        detail = ", ".join(f"{reg} x{n}" for reg, n
                           in sorted(unknown_trips.items()))
        notes.append(
            f"trip_count_unknown: {sum(unknown_trips.values())} while "
            f"body(ies) counted once ({detail}) — their regions' flops "
            "are lower bounds")
    if notes:
        regions["_notes"] = notes  # type: ignore[assignment]
    return regions


def collective_bytes_by_opcode(
    hlo_text: str,
) -> Dict[str, Dict[str, object]]:
    """Per-collective-opcode wire accounting for the fleet comms join
    (obs.fleet.comms): ``{opcode: {"bytes", "count", "regions":
    {full_scope_path: bytes}}}`` with ``while`` bodies multiplied by
    their trip count exactly like :func:`attribute_regions`.

    Bytes are the OUTPUT shape of each collective (the convention
    ``attribute_regions`` prices ``collective_bytes`` with), so the two
    views reconcile by construction.  Regions here are FULL scope paths
    (``region_of(..., depth=0)``): the comm attribution needs to see
    the ``comm/<kind>`` scope markers wherever they sit in the stack,
    which a report-depth truncation would cut off.
    """
    entry, comps = parse_hlo_computations(hlo_text)
    out: Dict[str, Dict[str, object]] = {}

    def account(instr: Instr, mult: float) -> None:
        b = _shape_bytes(instr.out_shapes) * mult
        row = out.setdefault(instr.opcode, {
            "bytes": 0.0, "count": 0.0, "regions": {},
        })
        row["bytes"] += b
        row["count"] += mult
        region = region_of(instr.op_name, depth=0)
        row["regions"][region] = row["regions"].get(region, 0.0) + b

    def walk(comp_name: str, mult: float, seen: Tuple[str, ...]) -> None:
        if comp_name not in comps or comp_name in seen:
            return
        for instr in comps[comp_name]:
            if instr.opcode in _COLLECTIVE_OPS:
                account(instr, mult)
                continue
            if instr.opcode == "while":
                trip = _while_trip_count(instr, comps) or 1
                for callee in instr.called:
                    walk(callee, mult * trip, seen + (comp_name,))
                continue
            if instr.called:
                # fusion/call/conditional/map bodies can all contain
                # collectives after SPMD partitioning; count each body
                # once at the caller's multiplicity.
                for callee in instr.called:
                    walk(callee, mult, seen + (comp_name,))
    walk(entry, 1.0, ())
    return out


def stage_hlo_text(stage) -> str:
    """Optimized HLO text (with op_name metadata) for a jax Lowered or
    Compiled stage.  A Lowered's ``as_text()`` is StableHLO (no HLO
    metadata), so it compiles first — pass an already-Compiled stage
    to avoid paying the compile twice."""
    txt = None
    if hasattr(stage, "as_text"):
        try:
            txt = stage.as_text()
        except Exception:  # noqa: BLE001 — fall through to compile
            txt = None
    if txt and txt.lstrip().startswith("HloModule"):
        return txt
    if hasattr(stage, "compile"):
        return stage.compile().as_text()
    raise TypeError(
        f"cannot extract HLO text from {type(stage).__name__}"
    )
