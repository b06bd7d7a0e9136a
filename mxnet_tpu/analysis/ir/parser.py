"""Text-level StableHLO module parser — no MLIR dependency.

The compile ledger canonicalizes and sha256-fingerprints every lowered
module (PR 10); this module is that seam grown into a real
parser: the canonicalizer (hardened here — nested ``loc(...)``, string
attributes, ``#loc`` reference lines), tensor-type decoding, entry-function
argument attributes (``tf.aliasing_output`` / ``jax.buffer_donor`` — the
donation story), constants with byte sizes, custom_call targets, and
collective ``replica_groups``.

Everything is line-oriented regex over the canonicalized text, which is
exactly as strong as it needs to be: the ledger retains the *canonicalized*
module (one op per line, attrs on the op line — the MLIR generic printer
contract jax's ``Lowered.as_text()`` follows), and a line the parser cannot
read is skipped, never fatal — a linter must not die on the program it
lints.

Deliberately dependency-free (stdlib only) and telemetry-free: the parser
is imported both by the offline ``mxlint --ir`` scanner (bare python, no
jax) and by the compile ledger's live guard (inside the serving process).
"""
from __future__ import annotations

import hashlib
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["canonicalize", "fingerprint", "parse_tensor_type",
           "dtype_nbytes", "count_aliased_args", "IRModule", "IROp",
           "IRArg", "IRConstant"]

#: identifier characters that, immediately before ``loc(``, mean the token
#: is part of a longer name (``alloc(``) and must not be stripped
_IDENT = re.compile(r"[A-Za-z0-9_.$]")


def canonicalize(text: str) -> str:
    """Strip MLIR location metadata so the text depends on the program
    alone: ``loc(...)`` spans (balanced parens, nested ``callsite``/
    ``fused`` forms included) and whole ``#loc`` reference lines.

    Hardened over the original single-regex pass (PR 10): nested
    parentheses inside ``loc(...)`` are matched, string literals are
    honored on both sides (a ``loc(`` *inside* a string attribute is
    payload, not metadata; a ``")"`` inside a loc's string doesn't
    terminate the span), and identifier-prefixed matches (``alloc(``) are
    left alone. For text with no location metadata the output is
    byte-identical to the input modulo the trailing newline — the property
    that keeps every committed fingerprint valid.
    """
    out: List[str] = []
    i, n = 0, len(text)
    in_str = False
    while i < n:
        ch = text[i]
        if in_str:
            out.append(ch)
            if ch == "\\" and i + 1 < n:      # escaped char, incl. \"
                out.append(text[i + 1])
                i += 2
                continue
            if ch == '"':
                in_str = False
            i += 1
            continue
        if ch == '"':
            in_str = True
            out.append(ch)
            i += 1
            continue
        if text.startswith("loc(", i) and \
                (i == 0 or not _IDENT.match(text[i - 1])):
            # consume the balanced span, honoring strings inside it
            j = i + 4
            depth = 1
            s = False
            while j < n and depth:
                c = text[j]
                if s:
                    if c == "\\":
                        j += 1
                    elif c == '"':
                        s = False
                elif c == '"':
                    s = True
                elif c == "(":
                    depth += 1
                elif c == ")":
                    depth -= 1
                j += 1
            # also drop the run of spaces/tabs that preceded the span
            # (mirrors the original `\s*loc\(...\)` strip)
            while out and out[-1] in (" ", "\t"):
                out.pop()
            i = j
            continue
        out.append(ch)
        i += 1
    lines = [ln for ln in "".join(out).splitlines()
             if not ln.lstrip().startswith("#loc")]
    return "\n".join(lines)


def fingerprint(text: str) -> str:
    """sha256 of the canonicalized module text — the compile ledger's
    content address (``compile_ledger.fingerprint_text`` delegates here)."""
    return hashlib.sha256(canonicalize(text).encode("utf-8")).hexdigest()


# -- tensor types ------------------------------------------------------------

#: element byte widths for the dtypes XLA programs actually carry
_DTYPE_BYTES = {
    "f64": 8, "i64": 8, "ui64": 8, "c64": 8,
    "f32": 4, "i32": 4, "ui32": 4,
    "f16": 2, "bf16": 2, "i16": 2, "ui16": 2,
    "i8": 1, "ui8": 1, "i4": 1, "ui4": 1, "i1": 1, "i2": 1,
    "c128": 16,
    "f8E4M3FN": 1, "f8E5M2": 1, "f8E4M3B11FNUZ": 1, "f8E4M3FNUZ": 1,
    "f8E5M2FNUZ": 1, "f8E8M0FNU": 1, "f4E2M1FN": 1,
}

_TENSOR_RE = re.compile(r"tensor<([^<>]*)>")


def dtype_nbytes(dtype: str) -> Optional[int]:
    return _DTYPE_BYTES.get(dtype)


def parse_tensor_type(spec: str) -> Optional[Tuple[Tuple, str]]:
    """``'4x8xf32'`` -> ``((4, 8), 'f32')``; ``'f32'`` -> ``((), 'f32')``.
    Dynamic dims (``?``) become ``None``. Returns None for forms that are
    not a plain ranked tensor spec."""
    spec = spec.strip()
    if not spec:
        return None
    parts = spec.split("x")
    dims: List[Optional[int]] = []
    k = 0
    for p in parts:
        if p == "?":
            dims.append(None)
            k += 1
        elif p.isdigit():
            dims.append(int(p))
            k += 1
        else:
            break
    dtype = "x".join(parts[k:])
    if not dtype or "<" in dtype or ">" in dtype:
        return None
    return tuple(dims), dtype


def _tensor_nbytes(shape: Tuple, dtype: str) -> Optional[int]:
    per = dtype_nbytes(dtype)
    if per is None:
        return None
    n = per
    for d in shape:
        if d is None:
            return None
        n *= d
    return n


# -- entry function arguments ------------------------------------------------

class IRArg:
    """One entry-function argument: index, tensor type, and the attribute
    facts the rules care about."""

    __slots__ = ("index", "shape", "dtype", "aliasing_output", "buffer_donor",
                 "sharding")

    def __init__(self, index, shape=(), dtype="", aliasing_output=None,
                 buffer_donor=False, sharding=None):
        self.index = index
        self.shape = shape
        self.dtype = dtype
        #: output index this arg aliases (tf.aliasing_output), or None
        self.aliasing_output = aliasing_output
        #: jax.buffer_donor = true (donation requested, alias left to XLA)
        self.buffer_donor = buffer_donor
        self.sharding = sharding


_ARG_RE = re.compile(r"%arg(\d+):\s*tensor<([^<>]*)>\s*")
_ALIAS_ATTR_RE = re.compile(r"tf\.aliasing_output\s*=\s*(\d+)")
_DONOR_ATTR_RE = re.compile(r"jax\.buffer_donor\s*=\s*true")
_SHARDING_ATTR_RE = re.compile(r'mhlo\.sharding\s*=\s*"([^"]*)"')


def _scan_attr_dict(s: str, pos: int) -> str:
    """The balanced ``{...}`` attribute dict starting at ``pos`` (or "" when
    none starts there). String-literal aware, because sharding annotations
    carry braces inside quotes (``mhlo.sharding = "{devices=[4,1]<=[4]}"``)
    — the case a flat ``\\{[^{}]*\\}`` regex silently truncates, which would
    lose the very ``tf.aliasing_output`` attr IR1000 keys on."""
    if pos >= len(s) or s[pos] != "{":
        return ""
    depth = 0
    in_str = False
    i = pos
    while i < len(s):
        c = s[i]
        if in_str:
            if c == "\\":
                i += 1
            elif c == '"':
                in_str = False
        elif c == '"':
            in_str = True
        elif c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
            if depth == 0:
                return s[pos:i + 1]
        i += 1
    return s[pos:]


def _iter_args(sig: str):
    """``(index, tensor_spec, attr_dict_text)`` per entry argument."""
    for m in _ARG_RE.finditer(sig):
        yield int(m.group(1)), m.group(2), _scan_attr_dict(sig, m.end())


def count_aliased_args(text: str) -> int:
    """Fast path for the live guard's donation assertion: how many entry
    arguments carry ``tf.aliasing_output`` or ``jax.buffer_donor`` — zero
    with donation requested is the silently-dropped case (IR1000)."""
    sig = text
    for ln in text.splitlines():
        if "func.func" in ln and "@main(" in ln:
            sig = ln.split(" -> ")[0]
            break
    n = 0
    for _idx, _spec, attrs in _iter_args(sig):
        if _ALIAS_ATTR_RE.search(attrs) or _DONOR_ATTR_RE.search(attrs):
            n += 1
    return n


# -- ops ---------------------------------------------------------------------

class IROp:
    """One op occurrence, as much of it as one line shows."""

    __slots__ = ("name", "dialect", "line", "raw", "operand_types",
                 "result_types", "replica_groups", "source_target_pairs",
                 "custom_target")

    def __init__(self, name, dialect, line, raw):
        self.name = name
        self.dialect = dialect
        self.line = line            # 1-based line in the module text
        self.raw = raw
        self.operand_types: List[Tuple[Tuple, str]] = []
        self.result_types: List[Tuple[Tuple, str]] = []
        self.replica_groups: Optional[List[List[int]]] = None
        self.source_target_pairs: Optional[List[List[int]]] = None
        self.custom_target: Optional[str] = None


class IRConstant:
    """One ``stablehlo.constant`` (or ``dense_resource``) with its decoded
    result size — the baked-in-weights signal."""

    __slots__ = ("line", "shape", "dtype", "nbytes", "raw")

    def __init__(self, line, shape, dtype, nbytes, raw):
        self.line = line
        self.shape = shape
        self.dtype = dtype
        self.nbytes = nbytes
        self.raw = raw


_OP_RE = re.compile(
    r'^\s*(?:%[\w#:,\s]+=\s*)?"?(stablehlo|mhlo|chlo)\.([a-z0-9_]+)"?')
_CUSTOM_TARGET_RE = re.compile(
    r'custom_call\s*@([\w.$-]+)|call_target_name\s*=\s*"([^"]+)"')
_REPLICA_GROUPS_RE = re.compile(r"replica_groups\s*=\s*dense<([^>]*)>")
_STP_RE = re.compile(r"source_target_pairs\s*=\s*dense<([^>]*)>")
_MODULE_ATTR_RE = re.compile(
    r"mhlo\.num_(partitions|replicas)\s*=\s*(\d+)")
_TYPESIG_RE = re.compile(r":\s*(\([^()]*\)\s*->\s*.+|[^()]+)$")

#: ops that move data across participants — IR1004's subjects
COLLECTIVE_OPS = frozenset((
    "all_reduce", "all_gather", "all_to_all", "reduce_scatter",
    "collective_permute", "collective_broadcast"))

#: ops that are a host round-trip by themselves
HOST_OPS = frozenset(("infeed", "outfeed", "send", "recv"))


def _parse_groups(body: str) -> Optional[List[List[int]]]:
    """``'[[0, 2], [1, 3]]'`` (or ``'0'``) -> nested int lists."""
    import ast as _ast
    body = body.strip()
    if not body:
        return []
    try:
        v = _ast.literal_eval(body)
    except (ValueError, SyntaxError):
        return None
    if isinstance(v, int):
        return [[v]]
    out = []
    try:
        for g in v:
            out.append([int(x) for x in (g if isinstance(g, (list, tuple))
                                         else [g])])
    except (TypeError, ValueError):
        return None
    return out


def _parse_type_sig(raw: str, op: IROp):
    """Fill operand/result types from the trailing ``: (a, b) -> c`` (or
    ``: a``) signature when the line carries one."""
    m = _TYPESIG_RE.search(raw)
    if not m:
        return
    sig = m.group(1)
    if "->" in sig:
        lhs, rhs = sig.split("->", 1)
    else:
        lhs, rhs = "", sig
    for part, dest in ((lhs, op.operand_types), (rhs, op.result_types)):
        for t in _TENSOR_RE.finditer(part):
            tt = parse_tensor_type(t.group(1))
            if tt is not None:
                dest.append(tt)


class IRModule:
    """A parsed StableHLO module: entry args, ops, constants, collectives,
    custom_calls, and the ``mhlo.num_partitions/num_replicas`` attrs."""

    def __init__(self, text: str):
        self.text = text
        self.lines = text.splitlines()
        self.num_partitions = 1
        self.num_replicas = 1
        self.args: List[IRArg] = []
        self.ops: List[IROp] = []
        self.constants: List[IRConstant] = []
        self.collectives: List[IROp] = []
        self.custom_calls: List[IROp] = []
        self._parse()

    @property
    def device_count(self) -> int:
        return max(1, self.num_partitions) * max(1, self.num_replicas)

    @property
    def aliased_args(self) -> List[IRArg]:
        return [a for a in self.args
                if a.aliasing_output is not None or a.buffer_donor]

    def op_counts(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for op in self.ops:
            out[op.name] = out.get(op.name, 0) + 1
        return out

    def _parse(self):
        seen_main = False
        for lineno, raw in enumerate(self.lines, 1):
            s = raw.strip()
            if not s:
                continue
            if s.startswith("module"):
                for m in _MODULE_ATTR_RE.finditer(s):
                    if m.group(1) == "partitions":
                        self.num_partitions = int(m.group(2))
                    else:
                        self.num_replicas = int(m.group(2))
                continue
            if not seen_main and "func.func" in s and "@main(" in s:
                seen_main = True
                sig = s.split(" -> ")[0]       # args only, not results
                for idx, spec, attrs in _iter_args(sig):
                    tt = parse_tensor_type(spec) or ((), "")
                    al = _ALIAS_ATTR_RE.search(attrs)
                    sh = _SHARDING_ATTR_RE.search(attrs)
                    self.args.append(IRArg(
                        idx, tt[0], tt[1],
                        aliasing_output=int(al.group(1)) if al else None,
                        buffer_donor=bool(_DONOR_ATTR_RE.search(attrs)),
                        sharding=sh.group(1) if sh else None))
                continue
            m = _OP_RE.match(raw)
            if not m:
                continue
            op = IROp(m.group(2), m.group(1), lineno, s)
            _parse_type_sig(s, op)
            self.ops.append(op)
            if op.name == "constant" or "dense_resource" in s:
                # result type is the constant's own type
                tt = None
                tms = list(_TENSOR_RE.finditer(s))
                if tms:
                    tt = parse_tensor_type(tms[-1].group(1))
                if tt is not None:
                    self.constants.append(IRConstant(
                        lineno, tt[0], tt[1],
                        _tensor_nbytes(tt[0], tt[1]), s))
            if op.name in COLLECTIVE_OPS:
                g = _REPLICA_GROUPS_RE.search(s)
                if g:
                    op.replica_groups = _parse_groups(g.group(1))
                p = _STP_RE.search(s)
                if p:
                    op.source_target_pairs = _parse_groups(p.group(1))
                self.collectives.append(op)
            if op.name == "custom_call":
                t = _CUSTOM_TARGET_RE.search(s)
                if t:
                    op.custom_target = t.group(1) or t.group(2)
                self.custom_calls.append(op)
