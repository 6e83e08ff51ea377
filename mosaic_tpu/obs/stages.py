"""Stage names for device ops: from a compiled program's optimized HLO to
``{module: {op: stage}}``.

The join names its stages with ``jax.named_scope`` (``pip.cells``,
``pip.hash_probe``, ``pip.compact``, ``pip.tier1``, ``pip.writeback``,
``stream.fold``, ...; the raster tile's own are ``zonal.centers`` and
``zonal.fold``; the KNN block program's ``knn.gather``, ``knn.distance``,
``knn.topk`` (``knn.edges`` in the distance's place where the queries are
polygons), and ``knn.heads`` of the program that gathers a launch's
head rows). This libtpu's device trace does not carry them: an
``XLA Ops`` event is named by the HLO instruction's text
(``%fusion.504 = f32[4000000,153]{...} fusion(...)``) and the number
changes with every edit of the join. The scopes do survive in the
optimized HLO's ``metadata={op_name="jit(loop)/.../pip.tier1/dot_general"}``,
so the names are recovered there, after the fact:

- a program **registers** itself where it is first compiled or warmed
  (:func:`register`): the jitted function, the shapes of its arguments
  (``ShapeDtypeStruct`` trees with shardings — never a device buffer) and
  its static arguments. That is a dict insert; nothing is lowered.
- a trace reader asks for :func:`tables` with the module names it found on
  the trace's ``XLA Modules`` line (and the row counts it saw, so that of
  a serving ladder's 22 bucket programs only the dispatched ones are
  touched). Each matching program is lowered and compiled again — a
  persistent-cache hit where the cache is on — and its HLO text parsed.
  An untraced run never calls it (:func:`lowerings` counts).
- the persistent cache's key leaves metadata out (JAX's default, kept:
  moving a line must not cost every user a compile), so an executable
  from the cache carries the scope names of the commit that compiled
  it. Where those are not the names the lowering has now, the program is
  compiled once more under a key that adds the lowering's scoped op
  names — by the traced run alone, and cached for the next one.

An op's stage is the innermost ``pip.*``/``stream.*``/``zonal.*``/``knn.*`` component of its own
``op_name``; else, for a fusion, the commonest stage among the
instructions of its fused computation; else (compiler-made ops with no
metadata: x64 splits of a parameter, copies, bitcasts) the stage of the
ops that consume it, then of the ops it consumes; else ``unscoped``.

Keys are ``<instruction name> <output type>`` without the layout
(``fusion.504 f32[4000000,153]``) — what the benchmark's
``xplane.op_label`` makes of a trace event's name — because programs that
share a module name (one jitted join, many buckets) share a table, and
the row count in the type keeps their ops apart.
"""

from __future__ import annotations

import collections
import hashlib
import re
import threading

from ..runtime import telemetry as _telemetry

UNSCOPED = "unscoped"

#: registered programs, newest last; bounded like every compiled-program
#: cache (a server cycling indexes re-registers, the oldest fall out)
_MAX_PROGRAMS = 256
_PROGRAMS: "collections.OrderedDict[tuple, _Program]" = collections.OrderedDict()
_LOCK = threading.Lock()
_LOWERINGS = [0]
_RECOMPILED = [0]

_STAGE = re.compile(r"^(?:pip|stream|zonal|knn|overlay|proximity)\.[A-Za-z0-9_.]+$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.+)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
#: a name location of the lowered module's text (a file location,
#: ``loc("stream.py":12:3)``, has no ``(`` after its string)
_LOCATION = re.compile(r'loc\("([^"]*)"\(')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_REFERENCE = re.compile(r"%([\w.\-]+)")


class _Program:
    __slots__ = ("module", "fn", "args", "static", "rows", "table")

    def __init__(self, module, fn, args, static, rows):
        self.module, self.fn, self.args = module, fn, args
        self.static, self.rows = static, rows
        self.table: dict | None = None


def module_name(fn) -> str:
    """The HLO module name ``jax.jit`` gives ``fn``: ``jit_<name>``."""
    name = getattr(fn, "__name__", None) or "fn"
    return "jit_" + re.sub(r"[^A-Za-z0-9_.\-]", "_", name)


def register(fn, args, static: dict | None = None, *, rows=None) -> None:
    """Remember how to lower ``fn(*args, **static)`` again. ``args`` is a
    tuple of ``ShapeDtypeStruct`` trees (see :func:`shapes_of`); ``rows``
    the per-device row count of the program's batch axis, which a reader
    sees in the trace's op types. No lowering happens here."""
    static = dict(static or {})
    module = module_name(fn)
    # the newest registration of one (program, rows, statics) wins: it is
    # the one whose shapes the running process dispatches
    import jax

    statics = [
        a for a in jax.tree_util.tree_leaves(args) if not hasattr(a, "shape")
    ]
    key = (module, id(fn), rows, repr(sorted(static.items())), repr(statics))
    with _LOCK:
        _PROGRAMS.pop(key, None)
        _PROGRAMS[key] = _Program(module, fn, args, static, rows)
        while len(_PROGRAMS) > _MAX_PROGRAMS:
            _PROGRAMS.popitem(last=False)


def shapes_of(tree):
    """``tree`` with every array replaced by its ``ShapeDtypeStruct``
    (sharding kept, buffer dropped)."""
    import jax

    def one(a):
        if not hasattr(a, "shape") or not hasattr(a, "dtype"):
            return a  # a static argument passed by position
        # an uncommitted array goes where the program puts it: only a
        # committed one pins its sharding into the lowering
        committed = getattr(a, "committed", getattr(a, "_committed", False))
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=getattr(a, "sharding", None) if committed else None,
        )

    return jax.tree_util.tree_map(one, tree)


def registered() -> list:
    """``(module name, rows)`` of every registered program."""
    with _LOCK:
        return [(p.module, p.rows) for p in _PROGRAMS.values()]


def lowerings() -> int:
    """How many programs :func:`tables` has lowered in this process."""
    return _LOWERINGS[0]


def recompiled() -> int:
    """How many of those had to be compiled again, because the compile
    cache answered with an executable from before a scope changed."""
    return _RECOMPILED[0]


def clear() -> None:
    with _LOCK:
        _PROGRAMS.clear()


def tables(modules=None, rows=None) -> dict:
    """``{module name: {op key: stage}}`` for the registered programs
    whose module name is in ``modules`` (as on the trace's ``XLA Modules``
    line; a ``(fingerprint)`` suffix is dropped; None = all) and whose
    ``rows`` is in ``rows`` (None = any). Lowers and compiles each such
    program once; a program that no longer lowers is left out."""
    want = None if modules is None else {m.split("(", 1)[0] for m in modules}
    rows = None if rows is None else set(rows)
    with _LOCK:
        programs = [
            p for p in _PROGRAMS.values()
            if (want is None or p.module in want)
            and (rows is None or p.rows is None or p.rows in rows)
        ]
    out: dict = {}
    for p in programs:
        if p.table is None:
            _LOWERINGS[0] += 1
            try:
                text = _optimized_hlo(p)
            except Exception as e:  # lint: broad-except-ok (a trace reader's boundary: an op left without a stage beats a traced run without numbers; the failure is recorded)
                _telemetry.record(
                    "stages_lowering_failed", module=p.module, rows=p.rows,
                    error=repr(e)[:200],
                )
                continue
            p.table = parse_hlo(text)
        out.setdefault(p.module, {}).update(p.table)
    return out


def _scopes(names) -> set:
    return {s for s in map(stage_of, names) if s}


def _optimized_hlo(p: _Program) -> str:
    """The program's optimized HLO text, with the scope names its source
    has now."""
    lowered = p.fn.lower(*p.args, **p.static)
    text = lowered.compile().as_text()
    named = sorted({
        n for n in _LOCATION.findall(lowered.as_text(debug_info=True))
        if stage_of(n)
    })
    if _scopes(_OP_NAME.findall(text)) == _scopes(named):
        return text
    # the cache answered with an executable compiled before a scope was
    # added or renamed (equal HLO but for metadata gives an equal key).
    # Compile again under a key that adds the lowering's scoped op names
    # — not its file names and line numbers, which JAX's own
    # metadata-in-key option would add: a checkout elsewhere, or a moved
    # line, finds the entry again.
    from jax._src import cache_key

    _RECOMPILED[0] += 1
    _telemetry.record("stages_stale_executable", module=p.module, rows=p.rows)
    salt = hashlib.sha256("\n".join(named).encode()).hexdigest()
    hook = cache_key.custom_hook
    cache_key.custom_hook = lambda: f"{hook()}mosaic.stages:{salt}"
    try:
        # the option changes nothing but makes `compile` build again
        # instead of handing back the lowering's executable
        return lowered.compile(
            compiler_options={"xla_embed_ir_in_executable": False}
        ).as_text()
    finally:
        cache_key.custom_hook = hook


def op_key(text: str) -> str:
    """``%fusion.504 = f32[4000000,153]{0,1:T(8,128)} fusion(...)`` ->
    ``fusion.504 f32[4000000,153]`` (the benchmark's ``xplane.op_label``)."""
    head, sep, rest = text.partition(" = ")
    short = head.strip().lstrip("%") or text[:48]
    if not sep:
        return short
    out = rest.strip().split("{", 1)[0].split(" ", 1)[0]
    return f"{short} {out}"[:96] if out else short


def stage_of(op_name: str) -> str | None:
    """The innermost ``pip.*``/``stream.*``/``zonal.*`` scope of an
    ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        if _STAGE.match(part):
            return part
    return None


def _commonest(stages) -> str | None:
    counts = collections.Counter(s for s in stages if s)
    return counts.most_common(1)[0][0] if counts else None


def parse_hlo(text: str) -> dict:
    """``{op key: stage}`` for every instruction of an optimized HLO
    module's text."""
    comps: dict = {}  # computation -> [(name, rest-of-line)]
    cur = None
    for line in text.splitlines():
        if cur is None:
            m = _COMPUTATION.match(line)
            if m:
                cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        else:
            m = _INSTRUCTION.match(line)
            if m:
                cur.append((m.group(1), m.group(2)))
    own: dict = {}  # instruction -> stage from its own metadata
    for instrs in comps.values():
        for name, rest in instrs:
            m = _OP_NAME.search(rest)
            own[name] = stage_of(m.group(1)) if m else None
    stage: dict = {}
    caller: dict = {}  # computation -> the instruction that calls it
    for instrs in comps.values():
        operands: dict = {}
        for name, rest in instrs:
            s = own[name]
            body = rest.split(", metadata=", 1)[0]
            refs = _REFERENCE.findall(body)
            for r in refs:
                if r in comps:
                    caller[r] = name
            called = _CALLS.search(rest)
            if s is None and called and called.group(1) in comps:
                s = _commonest(own[n] for n, _r in comps[called.group(1)])
            stage[name] = s
            operands[name] = [r for r in refs if r in own and r != name]
        users: dict = {}
        for name, ops in operands.items():
            for o in ops:
                users.setdefault(o, []).append(name)
        # compiler-made ops with no metadata: first what consumes them,
        # then what they consume, until nothing changes
        for neighbours in (users, operands):
            changed = True
            while changed:
                changed = False
                for name, _rest in instrs:
                    if stage[name] is None:
                        s = _commonest(
                            stage.get(n) for n in neighbours.get(name, ())
                        )
                        if s:
                            stage[name], changed = s, True
    home = {n: comp for comp, instrs in comps.items() for n, _r in instrs}
    table: dict = {}
    for comp, instrs in comps.items():
        for name, rest in instrs:
            # what is left inside a fused or applied computation belongs
            # to the instruction that calls it
            s, up = stage[name], comp
            while s is None and up in caller:
                s, up = stage[caller[up]], home[caller[up]]
            table[op_key(f"%{name} = {rest}")] = s or UNSCOPED
    return table
