"""Device time by the part of the model that spent it.

A profiler trace names a device operation by its compiled instruction
(``%fusion.417 = bf16[...] fusion(...)``) and carries nothing else: no
scope, no source line. The compiled program's text does
(``metadata={op_name="jit(_serve_step)/Llama/layer3/attn/dot_general"}``
on every instruction), and only the program knows which programs it ran
with which shapes. This module is the program's own map from one to the
other, in three steps.

:func:`note` keeps ``(function, static arguments, abstract arguments)``
of a jitted program, called only from a dispatch that traced (a new
variant of a program: :func:`obs.jitwatch.dispatch_span` and the loops'
tallies know). The entry holds shapes, dtypes and shardings, never a
buffer, so an engine or a trainer can go while its programs stay noted.

:func:`build` compiles every noted program again from its abstract
arguments (a hit in the process's persistent compile cache), reads
``as_text()`` once and keeps, for every instruction the device can
execute, its result shape, its scope path and its *part*. :func:`maps`
is the process-wide result, built lazily; nothing here runs on a loop's
thread while it serves.

:func:`join` takes device events ``(module, instruction text, start,
end)`` and gives every busy instant to the innermost event that covers
it and that event to its part, so the parts partition the busy time.

The parts are a fixed set. ``mixer`` is what mixes positions (attention
of every kind, MLA, Mamba, short convolution, retention), ``ffn`` the
dense MLP and the experts with their router, ``cache_write`` the write
of new cache rows and state, ``head`` the final norm, the vocabulary
product and the choice of a token, ``optimizer`` and ``grad_reduce`` a
training step's update and gradient exchange; an instruction of a
differentiated function is ``backward`` (``transpose(`` in its
``op_name``) or ``forward`` (``jvp(``); ``other`` lies under a scope
that names none of these, ``unscoped`` has no scope at all, is unknown
to the map, or is ambiguous. :data:`COMPONENTS` maps a scope component
to its part, and the deepest component that names one wins: a model
that adds a scope adds a line there and bumps :data:`VERSION`.
"""

from __future__ import annotations

import collections
import logging
import re
import threading
import time

log = logging.getLogger(__name__)

# Part of the persistent compile cache's key
# (``runtime/device.configure_compile_cache``): JAX leaves metadata out
# of the key, so an executable cached before a scope was added would be
# loaded with the old ``op_name``s. Bump when :data:`COMPONENTS` or a
# program's scopes change.
VERSION = "scopes-1"

SERVE_PARTS = ("mixer", "ffn", "cache_write", "head")
TRAIN_PARTS = ("forward", "backward", "optimizer", "grad_reduce")
PARTS = SERVE_PARTS + TRAIN_PARTS + ("other", "unscoped")

# scope component -> part; one line a scope a model opens (or a flax
# module name that stands for one)
COMPONENTS = {
    # what mixes positions
    "mixer": "mixer", "attn": "mixer", "attn_window": "mixer",
    "attn_full": "mixer",
    "mla_decode": "mixer", "mla_prefill": "mixer", "mamba": "mixer",
    "conv": "mixer", "retention": "mixer",
    # what works on a position alone
    "ffn": "ffn", "dense_ffn": "ffn", "mlp": "ffn", "moe": "ffn",
    "shared_expert": "ffn",
    # the serve programs' other parts
    "cache_write": "cache_write", "head": "head",
    # a training step's
    "optimizer": "optimizer", "grad_reduce": "grad_reduce",
}

# programs that are one part whole: the serve engine's copies between
# a row, the batch cache and the block store, and its slot-state writes
PROGRAMS = {
    "_insert_row": "cache_write", "_write_rows": "cache_write",
    "_write_block_rows": "cache_write", "_zero_cache": "cache_write",
    "_save_blocks": "cache_write", "_restore_blocks": "cache_write",
}

_WRAPPED = re.compile(r"^\w+\((.*)\)$")


def _bare(component: str) -> str:
    """``transpose(jvp(attn))`` -> ``attn``."""
    while True:
        m = _WRAPPED.match(component)
        if m is None:
            return component
        component = m.group(1)


def layer_part(scope: str) -> str:
    """The part the deepest component of a scope path names, whatever
    JAX wrapped around it; ``other`` where none does."""
    for comp in reversed(scope.split("/")):
        part = COMPONENTS.get(comp) or COMPONENTS.get(_bare(comp))
        if part is not None:
            return part
    return "other"


def classify(op_name: str) -> tuple:
    """``(scope path, part)`` of an instruction's ``op_name``. The last
    component is the primitive and is dropped; a name with no scope (an
    argument's, a compiler's own) is ``unscoped``."""
    scope, _, _ = op_name.rpartition("/")
    if not scope or "(" not in scope.partition("/")[0]:
        return "", "unscoped"
    part = PROGRAMS.get(_bare(scope.partition("/")[0])) or layer_part(scope)
    if part not in ("optimizer", "grad_reduce", "cache_write"):
        if "transpose(" in scope:
            part = "backward"
        elif "jvp(" in scope:
            part = "forward"
    return scope, part


# -- the compiled text ------------------------------------------------------

_HEADER = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OPCODE = re.compile(r"^\s*([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_REF = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|"
    r"false_computation)=%?([\w.\-]+)")
_CALL_LIST = re.compile(
    r"\b(?:branch_computations|called_computations)=\{([^}]*)\}")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_NUMBERED = re.compile(r"(?:\.\d+)+$")
# opcodes the device gives no time of their own
_NO_TIME = frozenset(("parameter", "constant", "get-tuple-element", "tuple",
                      "bitcast"))


def _closing(text: str, at: int) -> int:
    """Index just past the parenthesis that closes the one at ``at``."""
    depth = 0
    for i in range(at, len(text)):
        c = text[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return i + 1
    return len(text)


def bare_shape(shape: str) -> str:
    """A result shape without its layout: ``bf16[8,256]{1,0:T(8,128)}``
    -> ``bf16[8,256]`` (a trace event prints the same shape as the
    compiled text, but tilings may be spelled apart)."""
    while True:
        out = _LAYOUT.sub("", shape)
        if out == shape:
            return out.replace(" ", "")
        shape = out


def split_instruction(text: str):
    """``(name, result shape, opcode, rest)`` of one instruction's text
    (a line of ``as_text()`` or a trace event's name), or None."""
    m = _INSTR.match(text if text[:1] in " \t" else " " + text)
    if m is None:
        return None
    name, rhs = m.groups()
    end = _closing(rhs, 0) if rhs.startswith("(") else rhs.find(" ")
    if end <= 0:
        return None
    shape, rest = rhs[:end], rhs[end:]
    op = _OPCODE.match(rest)
    return name, bare_shape(shape), op.group(1) if op else "", rest


class _Instr:
    __slots__ = ("name", "shape", "opcode", "op_name", "operands",
                 "called", "scope", "part")

    def __init__(self, name, shape, opcode, rest) -> None:
        self.name, self.shape, self.opcode = name, shape, opcode
        m = _OP_NAME.search(rest)
        self.op_name = m.group(1).replace("\\'", "'") if m else ""
        start = rest.find("(")
        end = _closing(rest, start) if start >= 0 else 0
        self.operands = _REF.findall(rest[start:end])
        attrs = rest[end:]
        self.called = _CALLS.findall(attrs)
        for group in _CALL_LIST.findall(attrs):
            self.called += _REF.findall(group)
        self.scope, self.part = classify(self.op_name)


def _computations(text: str) -> tuple:
    """``(module name, entry computation, {computation: [_Instr]})``."""
    module, entry, comps, cur = "", "", {}, None
    for line in text.splitlines():
        if cur is None:
            if line.startswith("HloModule "):
                module = line.split()[1].rstrip(",")
                continue
            m = _HEADER.match(line)
            if m is not None:
                cur = comps.setdefault(m.group(2), [])
                if m.group(1):
                    entry = m.group(2)
            continue
        if line.startswith("}"):
            cur = None
            continue
        got = split_instruction(line)
        if got is not None:
            cur.append(_Instr(*got))
    return module, entry, comps


def _commonest(parts) -> str:
    """The commonest of the parts that say something, else ''."""
    seen = collections.Counter(p for p in parts if p and p != "unscoped")
    return seen.most_common(1)[0][0] if seen else ""


def _fused_part(instr: _Instr, comps: dict, depth: int = 0) -> tuple:
    """For a fusion with no scope of its own: the commonest part among
    its fused computation's instructions, with a scope that has it."""
    inner = [i for c in instr.called for i in comps.get(c, ())]
    if depth < 4:
        for i in inner:
            if i.part == "unscoped" and i.opcode == "fusion":
                i.scope, i.part = _fused_part(i, comps, depth + 1)
    part = _commonest(i.part for i in inner)
    if not part:
        return "", "unscoped"
    return next(i.scope for i in inner if i.part == part), part


def _inherit(instrs: list) -> None:
    """What the compiler put in carries no ``op_name`` (a prefetch's
    ``copy-start``/``copy-done``, a ``slice-start``/``slice-done``, a
    ``bitcast``): it belongs to what consumes it, else to what it
    consumes. A few passes, so that a start reaches its done's user."""
    by_name = {i.name: i for i in instrs}
    users = collections.defaultdict(list)
    for i in instrs:
        for o in i.operands:
            if o in by_name:
                users[o].append(i)
    for _ in range(4):
        changed = False
        for i in instrs:
            if i.part != "unscoped" or i.opcode in ("parameter", "constant"):
                continue
            for near in (users[i.name],
                         [by_name[o] for o in i.operands if o in by_name]):
                part = _commonest(n.part for n in near)
                if part:
                    src = next(n for n in near if n.part == part)
                    i.scope = "<-" + src.scope.removeprefix("<-")
                    i.part = part
                    changed = True
                    break
        if not changed:
            return


def parse(text: str, whole: str = "") -> tuple:
    """``(module name, {instruction name: (result shape, scope path,
    part)})`` for every instruction the device can give time to: those
    of the entry computation and of every computation reached from it
    through a ``while``, a ``conditional`` or a call. A fusion is its
    own ``op_name``; without one it is the commonest part among its
    fused computation's instructions; what still has none inherits from
    its users, then its operands (scope path prefixed ``<-``). ``whole``
    names the part of a program that is one part (:data:`PROGRAMS`)."""
    module, entry, comps = _computations(text)
    if whole:
        for instrs in comps.values():
            for i in instrs:
                i.scope, i.part = i.scope or module, whole
    out, seen, todo = {}, set(), [entry] if entry else []
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        instrs = comps[name]
        for i in instrs:
            if i.opcode == "fusion":
                if i.part == "unscoped":
                    i.scope, i.part = _fused_part(i, comps)
            else:
                todo += i.called
        _inherit(instrs)
        for i in instrs:
            if i.opcode not in _NO_TIME:
                out[i.name] = (i.shape, i.scope, i.part)
    return module, out


# -- note -------------------------------------------------------------------

class _Noted:
    __slots__ = ("fn", "name", "args", "kwargs")

    def __init__(self, fn, name, args, kwargs) -> None:
        self.fn, self.name, self.args, self.kwargs = fn, name, args, kwargs


_lock = threading.Lock()
_noted: dict = {}      # key -> _Noted, in the order noted
_built: dict = {}      # key -> (module, instructions, report)
note_calls = 0         # how often note() ran (a steady loop adds none)


def _abstract(leaf):
    """A leaf without its buffer: shape, dtype, sharding and weak type
    of an array; a host scalar or a static value as it is."""
    import jax
    import numpy as np

    if isinstance(leaf, jax.Array):
        # an uncommitted array (``jnp.asarray`` of host data) lowers with
        # no sharding of its own; a struct that named one would lower to
        # another module, and miss the cache
        return jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype,
            sharding=leaf.sharding if getattr(leaf, "committed", True)
            else None,
            weak_type=getattr(leaf, "weak_type", False))
    if isinstance(leaf, np.ndarray):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype)
    return leaf


def _leaf_key(leaf):
    sharding = getattr(leaf, "sharding", None)
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        return (tuple(leaf.shape), str(leaf.dtype), str(sharding),
                bool(getattr(leaf, "weak_type", False)))
    try:
        hash(leaf)
        return leaf
    except TypeError:
        return repr(leaf)


def note(fn, args: tuple = (), kwargs: dict | None = None) -> bool:
    """Keep a jitted program with the arguments of a call that traced
    it, every array replaced by its ``jax.ShapeDtypeStruct`` (sharding
    kept; a donated argument that the call deleted still says these).
    Static arguments are kept as they are: they are hashable values and
    hold no buffer. Returns whether the variant is new. A function
    without ``lower`` (a plain wrapper around a jitted one) is not a
    program and is passed over."""
    global note_calls
    note_calls += 1
    if not hasattr(fn, "lower"):
        return False
    import jax

    kwargs = kwargs or {}
    a_args, a_kwargs = jax.tree.map(_abstract, (tuple(args), dict(kwargs)))
    leaves, treedef = jax.tree.flatten((a_args, a_kwargs))
    name = getattr(fn, "__name__", None) or repr(fn)
    key = (id(fn), name, treedef, tuple(_leaf_key(x) for x in leaves))
    with _lock:
        if key in _noted:
            return False
        _noted[key] = _Noted(fn, name, a_args, a_kwargs)
    return True


def noted() -> list:
    """``[(program name, abstract positional arguments)]`` in the order
    noted."""
    with _lock:
        return [(n.name, n.args) for n in _noted.values()]


def reset() -> None:
    """Forget every noted program and every map (tests)."""
    global note_calls
    with _lock:
        _noted.clear()
        _built.clear()
        note_calls = 0


# -- build ------------------------------------------------------------------

def _compile_text(n: _Noted) -> tuple:
    """``(compiled text, cache, seconds)`` of one noted program.
    ``cache`` is ``memory`` where the process still held the executable
    (nothing compiled, nothing loaded), ``hit`` where the persistent
    cache had it, ``miss`` where the backend compiled."""
    from pytorch_distributed_nn_tpu.obs import jitwatch

    jitwatch.install()
    tot = jitwatch.thread_totals()
    mark = tot.mark()
    t0 = time.perf_counter()
    text = n.fn.lower(*n.args, **n.kwargs).compile().as_text()
    d = tot.since(mark)
    cache = "miss" if d["cache_misses"] or (
        d["compile"] > 0.0 and not d["cache_hits"]) \
        else "hit" if d["cache_hits"] else "memory"
    return text, cache, time.perf_counter() - t0


def _compile_text_uncached(n: _Noted) -> tuple:
    """The same with the persistent cache off for this one call: the
    text of a fresh compile always carries its metadata."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    t0 = time.perf_counter()
    try:
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        text = n.fn.lower(*n.args, **n.kwargs).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    return text, "bypassed", time.perf_counter() - t0


def build() -> dict:
    """Bring the maps up to date with what was noted and return them:
    ``{"modules": {module name: {instruction name: [(result shape,
    scope path, part)]}}, "programs": [{program, module, cache
    (``memory``, ``hit``, ``miss``, ``bypassed``), seconds,
    instructions}]}``. A module name's programs (a prefill's
    buckets) share one table; an instruction name lists each distinct
    reading once. Idempotent: a program is compiled and read once a
    process. Not for a loop's thread while it serves."""
    with _lock:
        todo = [(k, n) for k, n in _noted.items() if k not in _built]
    for key, n in todo:
        try:
            whole = PROGRAMS.get(n.name, "")
            text, cache, seconds = _compile_text(n)
            if "op_name=" not in text and not whole:
                # an executable loaded from the cache without metadata
                text, cache, more = _compile_text_uncached(n)
                seconds += more
            module, instrs = parse(text, whole)
        except Exception as e:  # noqa: BLE001 - a map must never break a run
            log.warning("scopes: no map for %s: %s", n.name, e)
            module, instrs, cache, seconds = "", {}, "failed", 0.0
        with _lock:
            _built[key] = (module, instrs, dict(
                program=n.name, module=module, cache=cache,
                seconds=seconds, instructions=len(instrs)))
    modules: dict = {}
    programs = []
    with _lock:
        built = [_built[k] for k in _noted if k in _built]
    for module, instrs, report in built:
        programs.append(report)
        table = modules.setdefault(module, {})
        for name, reading in instrs.items():
            seen = table.setdefault(name, [])
            if reading not in seen:
                seen.append(reading)
    return dict(version=VERSION, modules=modules, programs=programs)


def maps() -> dict:
    """The process-wide maps (:func:`build`, lazily)."""
    return build()


# -- join -------------------------------------------------------------------

def lookup(modules: dict, module: str, text: str) -> tuple:
    """``(instruction name, scope path, part, ambiguous)`` of one device
    event. Key: the module's name without fingerprint and the
    instruction's name; where the module's programs give the name two
    parts, the event's result shape decides; what is still two-valued
    (or unknown) is ``unscoped``."""
    got = split_instruction(text)
    name = got[0] if got else text.strip().lstrip("%").split(" ", 1)[0]
    readings = modules.get(module, {}).get(name)
    if not readings:
        return name, "", "unscoped", False
    if len({r[2] for r in readings}) > 1 and got:
        same = [r for r in readings if r[0] == got[1]]
        readings = same or readings
    if len({r[2] for r in readings}) > 1:
        return name, "", "unscoped", True
    return name, readings[0][1], readings[0][2], False


def lookup_name(modules: dict, name: str) -> tuple:
    """``(scope path, part)`` of an instruction name alone, over every
    module (a perfetto slice says no more): the one part every program
    that has the name agrees on, else ``unscoped``."""
    name = name.strip().lstrip("%")
    readings = [r for table in modules.values() for r in table.get(name, ())]
    parts = {r[2] for r in readings}
    if len(parts) != 1:
        return "", "unscoped"
    return readings[0][1], readings[0][2]


def join(ops: list, maps: dict) -> dict:
    """Device events of one chip ``[(module, instruction text, start,
    end)]`` (``module`` without its fingerprint, times in any one
    unit) against :func:`build`'s maps. Every busy instant belongs to
    the innermost event that covers it, the one that started last (a
    ``while``'s body operations lie inside the ``while``'s own event),
    so the parts partition the busy time: ``by_part`` sums to ``busy``,
    the union of the events. Also ``by_program`` ``{module: {part:
    (time, events)}}``, ``by_layer`` (a training step's ``forward`` and
    ``backward`` by the part of the model under them), ``by_name``
    ``{instruction name less its number: {part: time}}`` (what a
    breakdown's one name ``fusion`` is), the largest ``unscoped``
    instruction names, and the ``ambiguous`` time."""
    modules = maps.get("modules", {}) if maps else {}
    memo: dict = {}
    by_part: dict = collections.defaultdict(float)
    by_program: dict = {}
    by_layer: dict = collections.defaultdict(float)
    by_name: dict = collections.defaultdict(dict)
    unscoped: dict = collections.defaultdict(float)
    busy = ambiguous = 0.0
    stack: list = []   # open events by start: (end, reading, program)
    cursor = float("-inf")

    def advance(upto: float) -> None:
        # give [cursor, upto) to whoever is innermost, instant by instant
        nonlocal cursor, busy, ambiguous
        while stack:
            end, (name, scope, part, amb), prog = stack[-1]
            own = min(end, upto) - cursor
            if own > 0.0:
                cursor += own
                busy += own
                by_part[part] += own
                prog[part][0] += own
                kind = by_name[_NUMBERED.sub("", name)]
                kind[part] = kind.get(part, 0.0) + own
                if part == "unscoped":
                    unscoped[name] += own
                    ambiguous += own if amb else 0.0
                elif part in ("forward", "backward"):
                    by_layer[f"{part}/{layer_part(scope)}"] += own
            if end > upto:
                return
            stack.pop()
        cursor = upto   # nothing open: the chip is idle up to here

    for module, text, start, end in sorted(
            ops, key=lambda ev: (ev[2], -ev[3])):
        if end <= start:
            continue
        reading = memo.get((module, text))
        if reading is None:
            reading = memo[(module, text)] = lookup(modules, module, text)
        prog = by_program.setdefault(
            module, collections.defaultdict(lambda: [0.0, 0]))
        prog[reading[2]][1] += 1
        advance(start)
        stack.append((end, reading, prog))
    advance(float("inf"))
    return dict(
        busy=busy, by_part=dict(by_part), ambiguous=ambiguous,
        by_program={m: {p: tuple(v) for p, v in parts.items()}
                    for m, parts in by_program.items()},
        by_layer=dict(by_layer), by_name=dict(by_name),
        unscoped=sorted(unscoped.items(), key=lambda kv: -kv[1])[:10])
