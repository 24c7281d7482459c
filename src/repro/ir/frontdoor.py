"""Real-MLIR front door: tolerant ingestion of lowered MLIR text.

This is the layer that lets the served cost model eat programs it did
not generate: ``jax.jit(fn).lower().as_text()`` StableHLO, affine/scf
loop nests, arith, or the repo's own ``xpu`` printer output. Design
contract (the whole point of the module):

* **never raises on input.** Every entry point returns either a parsed
  :class:`IngestResult` or a structured :class:`IngestError` naming the
  stage that failed — malformed, truncated, or adversarial text is an
  expected input, not an exception path (the fuzz corpus in tests holds
  this property under hypothesis as well).
* **best-effort structural parse.** A line-oriented parser maps SSA ops
  onto the internal :class:`~repro.ir.graph.Graph` (opcode-mapped into
  the ``xpu`` dialect where known, name-preserved otherwise). When no
  structure is recoverable but the text still lexes, ingestion degrades
  to the raw :func:`~repro.core.tokenizer.tokenize_text` token stream —
  predictions still flow, keyed by a content hash of the tokens.
* **cache-compatible keys.** A parsed graph is keyed by its canonical
  ``struct_key()`` (so an ingested program and the same program built
  through the Graph API share LRU entries across the service, server,
  and replicated tier); the degraded path uses ``"text:" + sha1`` of
  the token stream, namespaced so it can never collide with a struct
  key (struct keys are 40 hex chars).

The serving integration (``predict_text`` on CostModelService /
CostModelServer / ReplicaClient) lives with each serving layer; this
module owns parsing, the error/result types, and the seeded fuzz-corpus
generator used by tests, the ``ingest`` benchmark, and
``launch/ingest.py``.
"""
from __future__ import annotations

import hashlib
import re
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import tokenizer as TOK
from repro.ir.graph import WHILE_OP, Graph, Tensor
from repro.obs.trace import annotate


# ------------------------------------------------------------ result types
@dataclass
class IngestError:
    """Structured ingestion failure; returned, never raised.

    ``stage`` says how far the text got: ``empty`` (no input), ``lex``
    (nothing tokenizable), ``parse`` (reserved for callers that require
    a structural graph), ``encode`` / ``predict`` (set by the serving
    layers when vocabulary or forward-pass handling fails)."""

    stage: str
    reason: str
    detail: str = ""

    def __repr__(self) -> str:  # compact: shows up in bench/CLI output
        d = f" ({self.detail})" if self.detail else ""
        return f"IngestError[{self.stage}] {self.reason}{d}"


@dataclass
class IngestResult:
    """A successfully ingested text: either a structural graph (with
    its canonical struct key) or the degraded token-stream form."""

    key: str                     # struct_key or "text:"+sha1(tokens)
    tokens: List[str]            # raw lexed tokens (fallback stream)
    graph: Optional[Graph]       # None -> token-stream-only ingestion
    dialects: Tuple[str, ...]    # dialect prefixes seen (sorted)
    n_ops: int                   # structural ops recovered (0 if none)


@dataclass
class TextEntry:
    """A featurized text: the ids-first batch entry plus ingest stats.

    Produced by ``CostModelService.ingest_text`` — ``(key, ids)`` slots
    straight into ``predict_entries`` / ``submit_entry`` / the replica
    wire format, so every cache layer treats ingested text exactly like
    a Graph submit."""

    key: str
    ids: "np.ndarray"
    n_tokens: int
    oov_rate: float              # fraction of tokens outside the vocab
    unk_rate: float              # fraction of ids collapsed to <unk>
    dialects: Tuple[str, ...] = ()
    n_ops: int = 0               # 0 -> token-stream fallback path


@dataclass
class TextPrediction:
    """predict_text() payload: denormalized predictions + ingest stats."""

    predictions: Dict[str, float]
    key: str
    n_tokens: int
    oov_rate: float              # fraction of tokens outside the vocab
    unk_rate: float              # fraction of ids collapsed to <unk>
    dialects: Tuple[str, ...] = ()
    n_ops: int = 0               # 0 -> token-stream fallback path


def prediction_from(entry: TextEntry,
                    predictions: Dict[str, float]) -> TextPrediction:
    """Attach denormalized head predictions to a featurized entry —
    shared by the service, the async server, and the replica client so
    all three tiers return identical payload shapes."""
    return TextPrediction(predictions=predictions, key=entry.key,
                          n_tokens=entry.n_tokens,
                          oov_rate=entry.oov_rate,
                          unk_rate=entry.unk_rate,
                          dialects=entry.dialects, n_ops=entry.n_ops)


# ------------------------------------------------------------- the parser
# `%out = "dialect.op"(...)` (generic) or `%out = dialect.op ...`
# (pretty); results may be a list (`%values, %indices = ...`) or carry
# a count (`%0:2 = ...`, used as `%0#1`).
_RESULTS = r"%[\w]+(?::\d+)?(?:\s*,\s*%[\w]+(?::\d+)?)*"
_OP_RE = re.compile(
    r'^\s*(' + _RESULTS + r')\s*=\s*'
    r'(?:"([A-Za-z_][\w$.]*)"|([A-Za-z_]\w*\.[\w.]+))\s*(.*)$')
# `%r = call @f(%a, %b) : ...` / `%r:2 = func.call @f(...)`
_CALL_RE = re.compile(
    r'^\s*(?:(' + _RESULTS + r')\s*=\s*)?(?:func\.)?call\s+@([\w$.\-]+)'
    r'\s*\(([^)]*)\)')
_RESULT_NAME_RE = re.compile(r"%([\w]+)")
_DEFINES_RE = re.compile(r"^\s*" + _RESULTS + r"\s*=")
_TYPE_RE = re.compile(r"(?:tensor|memref|vector)<([^>]*)>")
_OPERAND_RE = re.compile(r"%([A-Za-z0-9_]+)(?:#(\d+))?")
_RETURN_RE = re.compile(r"^\s*(?:func\.)?return\b(.*)$")
_FUNC_RE = re.compile(r"^\s*func\.func\s+(?:(?:public|private|nested)\s+)?"
                      r"@([\w$.\-]+)")
_BLOCK_ARGS_RE = re.compile(r"^\s*\^bb\d+\s*\((.*)\)\s*:")
_ITER_ARG_RE = re.compile(r"%([\w]+)\s*=\s*%([\w]+)(?:#\d+)?")
_CONST_INT_RE = re.compile(
    r"^\s*%([\w]+)\s*=\s*stablehlo\.constant\s+dense<(-?\d+)>\s*:"
    r"\s*tensor<[su]?i\d+>")
_COMPARE_RE = re.compile(
    r"stablehlo\.compare\s+(LT|LE|GT|GE),\s*%([\w]+),\s*%([\w]+)")
_APPLIES_RE = re.compile(r"\bapplies\s+(?:\w+\.)?(\w+)")
_SCALAR_DTYPES = ("bf16", "f64", "f32", "f16",
                  "i64", "i32", "i16", "i8", "i1")
MAX_INLINE_DEPTH = 32

# Op-name -> xpu opcode translations (StableHLO / arith / math / the
# repo's own printer) for ops the xpu dialect names otherwise; every
# other op keeps its own name, which the tokenizer resolves (OOV
# machinery) instead of collapsing it onto a different op.
OPCODE_MAP = {
    "dot_general": "matmul", "dot": "matmul", "einsum": "matmul",
    "convolution": "conv2d", "conv": "conv2d",
    "add": "add", "addf": "add", "addi": "add",
    "subtract": "sub", "subf": "sub", "subi": "sub",
    "multiply": "mult", "mulf": "mult", "muli": "mult",
    "divide": "div", "divf": "div", "divi": "div",
    "maximum": "maximum", "maxf": "maximum", "maximumf": "maximum",
    "minimum": "minimum", "minf": "minimum",
    "exponential": "exp", "exp": "exp", "negate": "neg", "abs": "abs",
    "tanh": "tanh", "logistic": "sigmoid", "rsqrt": "rsqrt",
    "reduce_sum": "reduce_sum", "reduce_max": "reduce_max",
    "broadcast_in_dim": "broadcast", "broadcast": "broadcast",
    "reshape": "reshape", "transpose": "transpose",
    "concatenate": "concat", "slice": "slice", "pad": "pad",
    "load": "slice", "store": "pad",      # affine/memref data movement
}
# ``stablehlo.reduce ... applies <op>``: the reduction's own kind
REDUCE_MAP = {"add": "reduce_sum", "maximum": "reduce_max",
              "minimum": "reduce_min", "multiply": "reduce_prod"}


def _parse_type(txt: str) -> Tensor:
    """Best-effort Tensor from one MLIR type spelling. Dynamic dims
    (``?``) become 1; unknown element types ride through as-is (the
    Graph layer is dtype-string tolerant)."""
    m = _TYPE_RE.search(txt)
    if m:
        parts = [p for p in m.group(1).split("x") if p]
        dims: List[int] = []
        dtype = "f32"
        for p in parts:
            if p.isdigit():
                dims.append(int(p))
            elif p == "?":
                dims.append(1)
            else:
                dtype = p.split(" ")[0].strip()
        return Tensor(tuple(dims), dtype)
    for d in _SCALAR_DTYPES:
        if re.search(rf"\b{d}\b", txt):
            return Tensor((), d)
    return Tensor((), "f32")


def _xpu_opcode(raw: str, rest: str = "") -> str:
    """Map ``dialect.op`` onto an xpu opcode; unknown names keep the
    sanitized op name (OOV-safe downstream). A pretty-printed reduce
    names its reduction (``applies stablehlo.add`` -> ``reduce_sum``)."""
    name = raw.rsplit(".", 1)[-1]
    if name == "reduce":
        m = _APPLIES_RE.search(rest)
        if m:
            return REDUCE_MAP.get(m.group(1), f"reduce_{m.group(1)}")
    return OPCODE_MAP.get(name, name)


def _head(lines: Sequence[str]) -> str:
    """A function's signature: its lines up to the body's brace."""
    return "\n".join(lines[:8]).split("{")[0] + "{"


def _signature_args(text: str) -> List[Tuple[str, Tensor]]:
    """(%name, type) pairs from func.func signatures (possibly spanning
    lines). Tolerant: a missing/garbled signature just yields []."""
    args: List[Tuple[str, Tensor]] = []
    for m in re.finditer(r"func\.func[^{]*", text):
        sig = m.group(0)
        for am in re.finditer(
                r"%([A-Za-z0-9_]+):\s*((?:tensor|memref|vector)<[^>]*>"
                r"|[a-z]\w*)", sig):
            args.append((am.group(1), _parse_type(am.group(2))))
    return args


def _functions(text: str) -> Tuple[Dict[str, List[str]], Optional[str]]:
    """Split a module into its functions' lines (signature first) and
    name the entry: ``@main``, else the first public function, else the
    first. Text with no ``func.func`` is one anonymous function."""
    funcs: Dict[str, List[str]] = {}
    order: List[Tuple[str, bool]] = []
    cur: Optional[List[str]] = None
    for line in text.splitlines():
        m = _FUNC_RE.match(line)
        if m:
            cur = funcs.setdefault(m.group(1), [])
            order.append((m.group(1), "private" not in line.split("@")[0]))
        elif cur is None:
            cur = funcs.setdefault("", [])
            order.append(("", True))
        cur.append(line)
    if not order:
        return funcs, None
    names = [n for n, _ in order]
    if "main" in funcs:
        return funcs, "main"
    public = [n for n, pub in order if pub and n]
    return funcs, (public[0] if public else names[0])


def _region_end(lines: Sequence[str], i: int) -> int:
    """Index past the last line of the regions that the op on line
    ``i`` opens (by brace depth; attribute dicts balance on a line)."""
    depth = lines[i].count("{") - lines[i].count("}")
    j = i + 1
    opened = depth > 0
    while j < len(lines):
        if depth <= 0 and opened:
            break
        depth += lines[j].count("{") - lines[j].count("}")
        opened = opened or depth > 0
        j += 1
    return j


def _int_constants(lines: Sequence[str]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for line in lines:
        m = _CONST_INT_RE.match(line)
        if m:
            out[m.group(1)] = int(m.group(2))
    return out


def while_trips(lines: Sequence[str], i: int,
                outer: Dict[str, int]) -> Optional[int]:
    """Static trip count of the ``stablehlo.while`` on line ``i``, or
    None. The loops ``lax.scan``/``fori_loop``/``map`` emit count one
    iteration argument from a constant start to a constant bound
    (``compare LT``/``LE`` in ``cond``) by a constant step (an ``add``
    of it in ``do``). ``outer`` holds the integer constants defined
    before the loop in its function."""
    head = lines[i]
    inits = dict(_ITER_ARG_RE.findall(head[head.find("("):]))
    end = _region_end(lines, i)
    body = lines[i + 1:end]
    split = next((k for k, ln in enumerate(body)
                  if re.match(r"^\s*\}\s*do\s*\{", ln)), None)
    if split is None:
        return None
    cond, do = body[:split], body[split:]
    cond_c = dict(outer, **_int_constants(cond))
    do_c = dict(outer, **_int_constants(do))
    for ln in cond:
        m = _COMPARE_RE.search(ln)
        if m is None:
            continue
        kind, a, b = m.groups()
        if a in inits and b in cond_c:
            arg, bound = a, cond_c[b]
        elif b in inits and a in cond_c and kind in ("GT", "GE"):
            arg, bound = b, cond_c[a]
            kind = "LT" if kind == "GT" else "LE"
        else:
            return None
        if kind not in ("LT", "LE") or inits[arg] not in outer:
            return None
        start = outer[inits[arg]]
        step = None
        for dl in do:
            am = re.search(r"stablehlo\.add\s+%([\w]+),\s*%([\w]+)", dl)
            if am is None or arg not in am.groups():
                continue
            other = am.group(2) if am.group(1) == arg else am.group(1)
            if other in do_c:
                step = do_c[other]
                break
        if not step or step <= 0:
            return None
        span = bound - start + (1 if kind == "LE" else 0)
        return max(0, -(-span // step))
    return None


@dataclass
class ParseStats:
    """What one parse recovered and what it could not: ``whiles`` and
    those of unknown trip count, and ``lossy`` op lines dropped or
    calls left un-inlined."""
    ops: int = 0
    whiles: int = 0
    unknown_trips: int = 0
    lossy: int = 0


def _lookup(env: Dict[str, int], name: str, idx: Optional[str]
            ) -> Optional[int]:
    if idx is not None and f"{name}#{idx}" in env:
        return env[f"{name}#{idx}"]
    return env.get(name)


def _operands(env: Dict[str, int], txt: str) -> List[int]:
    out = []
    for name, idx in _OPERAND_RE.findall(txt):
        v = _lookup(env, name, idx or None)
        if v is not None:
            out.append(v)
    return out


def _bind(env: Dict[str, int], results: str, vids: Sequence[int]) -> None:
    """Bind an op's result names: ``%a, %b`` one each; ``%0:2`` as
    ``%0`` and ``%0#k``."""
    names = _RESULT_NAME_RE.findall(results)
    if len(names) > 1:
        for k, n in enumerate(names):
            env[n] = vids[min(k, len(vids) - 1)]
        return
    env[names[0]] = vids[0]
    for k, v in enumerate(vids):
        env[f"{names[0]}#{k}"] = v


def _parse_function(g: Graph, funcs: Dict[str, List[str]], name: str,
                    env: Dict[str, int], stats: ParseStats,
                    stack: Tuple[str, ...]) -> List[int]:
    """Append one function's ops to ``g`` (``env`` binds its arguments)
    and return the values it returns; calls are inlined."""
    lines = funcs[name]
    returns: List[int] = []
    consts: Dict[str, int] = {}
    for i, line in enumerate(lines):
        if _FUNC_RE.match(line):
            continue
        bm = _BLOCK_ARGS_RE.match(line)
        if bm:                       # a region's block arguments
            if g.ops:
                for n in _RESULT_NAME_RE.findall(bm.group(1)):
                    env[n] = g.ops[-1].result
            continue
        rm = _RETURN_RE.match(line)
        if rm:
            returns = _operands(env, rm.group(1).split(" : ")[0])
            continue
        cm = _CALL_RE.match(line)
        if cm:
            results, callee, args = cm.groups()
            operands = _operands(env, args)
            if (callee in funcs and callee not in stack
                    and len(stack) < MAX_INLINE_DEPTH):
                sub = {n: v for (n, _), v in zip(
                    _signature_args(_head(funcs[callee])), operands)}
                outs = _parse_function(g, funcs, callee, sub, stats,
                                       stack + (callee,))
            else:
                stats.lossy += 1
                arrow = line.rsplit("->", 1)
                outs = [g.add_op("call", operands, _parse_type(
                    arrow[1] if len(arrow) == 2 else line))]
            if results and outs:
                _bind(env, results, outs)
            elif results:
                stats.lossy += 1
            continue
        m = _OP_RE.match(line)
        if m is None:
            if _DEFINES_RE.match(line):
                stats.lossy += 1      # a defining line we cannot read
            continue
        results, raw_op = m.group(1), m.group(2) or m.group(3)
        rest = m.group(4)
        cm_int = _CONST_INT_RE.match(line)
        if cm_int:
            consts[cm_int.group(1)] = int(cm_int.group(2))
        opcode = _xpu_opcode(raw_op, rest)
        attrs = {}
        if opcode == WHILE_OP:
            iters = _ITER_ARG_RE.findall(rest[rest.find("("):])
            operands = [env[init] for _, init in iters if init in env]
            trips = while_trips(lines, i, consts)
            attrs["trips"] = trips
            stats.whiles += 1
            stats.unknown_trips += trips is None
        else:
            # operands: %refs before the trailing type annotation
            operands = _operands(env, rest.split(" : ")[0])
        # result type: prefer the type after ->, else the last type in
        # the line, else scalar f32
        arrow = rest.rsplit("->", 1)
        t = _parse_type(arrow[1] if len(arrow) == 2 else rest)
        vid = g.add_op(opcode, operands, t, **attrs)
        _bind(env, results, [vid])
        if opcode == WHILE_OP:          # the body reads the loop's state
            for it, _ in iters:
                env[it] = vid
    return returns


def parse_mlir_stats(text: str) -> Tuple[Optional[Graph], ParseStats]:
    """:func:`parse_mlir` and what it recovered (:class:`ParseStats`)."""
    stats = ParseStats()
    try:
        funcs, entry = _functions(text)
        if entry is None:
            return None, stats
        g = Graph(name="ingested")
        env: Dict[str, int] = {}
        for name, t in _signature_args(_head(funcs[entry])):
            if name not in env:
                env[name] = g.add_arg(t)
        returns = _parse_function(g, funcs, entry, env, stats, (entry,))
        if not g.ops:
            return None, stats
        stats.ops = len(g.ops)
        g.outputs = returns or [g.ops[-1].result]
        g.validate()
        return g, stats
    except Exception:
        return None, stats


def parse_mlir(text: str) -> Optional[Graph]:
    """Best-effort structural parse of MLIR text into a Graph.

    Returns None when no SSA ops are recoverable (callers fall back to
    the token stream). Never raises: unparsable lines are skipped and
    unknown operand references are dropped from the op's operand list.
    The entry function's ops come in program order with every
    ``func.call`` replaced by its callee's ops; region ops (loop
    bodies, reduction bodies) follow the op that owns them. A
    ``stablehlo.while`` becomes a ``while`` op whose ``trips``
    attribute holds its static trip count (None where the bound is not
    a constant); its iteration arguments read the loop's state, the
    ``while`` op's result.
    """
    with annotate("frontdoor.parse") as sp:
        g, st = parse_mlir_stats(text)
        if sp is not None:
            sp.set_metadata(ops=st.ops, whiles=st.whiles,
                            unknown_trips=st.unknown_trips, lossy=st.lossy)
    return g


def _dialects(text: str) -> Tuple[str, ...]:
    seen = set(re.findall(
        r"\b(stablehlo|mhlo|affine|scf|arith|math|func|memref|linalg"
        r"|xpu|chlo|vhlo)\.", text))
    return tuple(sorted(seen))


def text_key(tokens: Sequence[str]) -> str:
    """Cache key for token-stream-only ingestion: content hash of the
    lexed stream (whitespace/formatting mutations collapse onto one
    entry), namespaced so it can't collide with 40-hex struct keys."""
    h = hashlib.sha1("\x00".join(tokens).encode("utf-8")).hexdigest()
    return f"text:{h}"


def ingest(text) -> "IngestResult | IngestError":
    """Parse arbitrary MLIR-ish text. Never raises.

    Structural parse first; token-stream fallback second; only inputs
    with no lexable content at all come back as an IngestError."""
    try:
        if not isinstance(text, str):
            if isinstance(text, (bytes, bytearray)):
                text = bytes(text).decode("utf-8", "replace")
            else:
                return IngestError("empty", "input is not text",
                                   type(text).__name__)
        if not text.strip():
            return IngestError("empty", "no input text")
        tokens = TOK.tokenize_text(text)
        # tokenize_text always adds BOS/EOS; anything else is content
        if len(tokens) <= 2:
            return IngestError("lex", "no tokenizable content",
                               f"{len(text)} chars")
        g = parse_mlir(text)
        if g is not None:
            return IngestResult(key=g.struct_key(), tokens=tokens,
                                graph=g, dialects=_dialects(text),
                                n_ops=len(g.ops))
        return IngestResult(key=text_key(tokens), tokens=tokens,
                            graph=None, dialects=_dialects(text),
                            n_ops=0)
    except Exception as e:      # absolute backstop: still structured
        return IngestError("lex", type(e).__name__, str(e)[:200])


# --------------------------------------------------------- example corpus
# A hand-written affine/scf loop nest: parser coverage for the paper's
# "lower-level dialects produce much larger sequences" scenario and a
# seed for dialect-mixing fuzz (nothing in the jnp pool lowers to
# affine, so this keeps that dialect exercised honestly).
AFFINE_EXAMPLE = """\
module {
  func.func @saxpy(%arg0: memref<256xf32>, %arg1: memref<256xf32>,
                   %arg2: f32) {
    affine.for %i = 0 to 256 {
      %0 = affine.load %arg0[%i] : memref<256xf32>
      %1 = affine.load %arg1[%i] : memref<256xf32>
      %2 = arith.mulf %0, %arg2 : f32
      %3 = arith.addf %2, %1 : f32
      affine.store %3, %arg1[%i] : memref<256xf32>
    }
    return
  }
  func.func @tile(%arg0: memref<64x64xf32>, %arg1: memref<64x64xf32>) {
    %c0 = arith.constant 0 : index
    scf.for %i = %c0 to %c0 step %c0 {
      %0 = affine.load %arg0[%i, %i] : memref<64x64xf32>
      %1 = arith.mulf %0, %0 : f32
      %2 = math.tanh %1 : f32
      affine.store %2, %arg1[%i, %i] : memref<64x64xf32>
    }
    return
  }
}
"""


# ------------------------------------------------------------ fuzz corpus
def mutate_text(text: str, rng: np.random.Generator) -> str:
    """One random mutation: truncation, byte substitution, line
    shuffling, char deletion, garbage injection, or dialect splicing."""
    kind = int(rng.integers(0, 7))
    if not text:
        return text
    if kind == 0:                               # hard truncation
        return text[: int(rng.integers(0, len(text)))]
    if kind == 1:                               # byte substitutions
        b = bytearray(text.encode("utf-8"))
        for _ in range(int(rng.integers(1, 8))):
            b[int(rng.integers(0, len(b)))] = int(rng.integers(0, 256))
        return b.decode("utf-8", "replace")
    if kind == 2:                               # shuffle lines
        lines = text.splitlines()
        rng.shuffle(lines)
        return "\n".join(lines)
    if kind == 3:                               # delete a char span
        i = int(rng.integers(0, len(text)))
        j = min(len(text), i + int(rng.integers(1, 64)))
        return text[:i] + text[j:]
    if kind == 4:                               # garbage injection
        junk = "".join(chr(int(c)) for c in rng.integers(1, 0x2FF, 16))
        i = int(rng.integers(0, len(text)))
        return text[:i] + junk + text[i:]
    if kind == 5:                               # dialect mixing
        lines = text.splitlines()
        extra = AFFINE_EXAMPLE.splitlines()
        i = int(rng.integers(0, len(lines) + 1))
        return "\n".join(lines[:i] + extra + lines[i:])
    return text + text[: int(rng.integers(0, len(text)))]  # duplication


def fuzz_corpus(seed_texts: Sequence[str], n: int,
                rng: np.random.Generator) -> List[str]:
    """``n`` mutated inputs from ``seed_texts``: every mutation kind
    above, stacked 1-3 deep, plus the degenerate empties. Deterministic
    given the rng state — tests and the bench share seeds."""
    out: List[str] = ["", " \n\t ", "\x00\xff\xfe", "%"]
    seeds = [s for s in seed_texts if s] or [AFFINE_EXAMPLE]
    while len(out) < n:
        t = seeds[int(rng.integers(0, len(seeds)))]
        for _ in range(int(rng.integers(1, 4))):
            t = mutate_text(t, rng)
        out.append(t)
    return out[:n]
