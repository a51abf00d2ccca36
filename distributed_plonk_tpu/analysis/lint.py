"""AST-level repo hazard lints (the sub-second half of the verifier).

Four lint families, each targeting a bug class this repo has actually
shipped or nearly shipped:

JIT01 jit-cache-key: a jit-compiled callable is stored in a cache dict
    (`self._fns[key] = jax.jit(...)` / `= (fn, consts)`) but the
    closure/partial it wraps depends on an enclosing-function local that
    is NOT derivable from the cache key — so two call sites that differ
    in that value silently share (or miss) a compiled program. This is
    the PR 3 bug class (digit extraction caching per exact width while
    warmup compiled another). Derivability is tracked through simple
    local assignments (`plain = boundary == "plain"` makes `plain`
    key-derived when `boundary` is in the key); `self` and module
    globals are allowed (per-instance caches are keyed by identity,
    globals are latched configuration).

PROM01/PROM02 dtype promotion: arithmetic in a kernel module mixing a
    bare Python float literal into (potentially traced) expressions —
    jnp promotes uint32 arrays to f32 silently — and any float64
    reference in kernel modules (the limb pipeline is 32-bit end to
    end).

LOCK01/LOCK02 lock discipline (service/ + store/): a self attribute of
    a class that owns a threading lock is mutated both inside and
    outside `with self._lock` scopes (LOCK01), or mutated outside the
    lock while another method READS it under the lock (LOCK02) —
    outside __init__ in both cases. Helper methods whose intra-class
    call sites are ALL lock-held count as lock-held themselves
    (fixpoint), so `_delete_locked`-style internals don't
    false-positive.

OBS01 metric glossary (service/ + runtime/ + store/ + obs/): a metric
    name recorded via a string-literal `.inc("name")` / `.observe("name")`
    must be documented in service/metrics.py's module docstring — the
    glossary is the operator's only map from a /metrics line to what
    the code actually counted, and undocumented names rot into
    write-only telemetry. Documented = the name (or a `family_*`
    wildcard covering it) appears on one of the docstring's indented
    glossary lines; names published through a scoped registry
    (Metrics.scoped) also pass when their store_-prefixed form is
    documented. F-string/derived names are out of scope (they are
    families; document the wildcard).

LOG01 structured-log subsystem glossary (same dirs as OBS01): the
    `subsystem` literal of every structured-log emission
    (`obs.log.emit("dispatcher", ...)` / `LogBuffer.emit(...)`) must be
    documented in obs/log.py's module docstring glossary — the
    subsystem field is how an operator slices the fleet's JSONL logs,
    and an undocumented (or typo'd) subsystem silently forks the
    vocabulary. Derived/variable subsystems are out of scope.

LOCK03 lock-acquisition order (same scope as LOCK01): a directed graph
    over (class, lock) nodes with an edge A -> B wherever code may
    acquire B while holding A — a nested `with self.<B>` inside
    `with self.<A>`, a multi-item `with self.A, self.B`, or a call made
    under A to a method (of this or any other linted class, matched by
    method name) that acquires B. Any cycle in that may-hold-while-
    acquiring relation is a deadlock two threads can reach by taking
    the locks in opposite orders; a self-edge on a plain Lock (not
    RLock) is the single-thread re-entry deadlock. `Condition(lock)`
    aliases the wrapped lock. Cross-class edges are name-matched (no
    type inference), so a shared method name can over-approximate — a
    pragma on any edge of a reported cycle breaks the cycle.

ENV01 knob glossary (whole package): every string literal naming a
    `DPT_*` environment knob must appear in the knob glossary held in
    constants.py's module docstring (same indented name-column format
    as the OBS01 metric glossary; a `DPT_FAMILY_*` token documents a
    family). The glossary is the single source of truth operators get
    for the ~100 knobs accreted across PRs; an undocumented knob is
    configuration surface nobody can discover. Derived names
    (`"DPT_TTL_%s_S" % cls`) are out of scope — document the wildcard.

TAG01 wire-tag conformance (repo-wide): every tag in
    runtime/protocol.py's TAG_NAMES table must be referenced by at
    least one encode/decode/dispatch site in the package outside
    protocol.py, AND by at least one test under tests/ (the old-peer
    ERR-degradation/back-compat reference) — a new JOIN/LEAVE/
    AGGREGATE-style tag that lands without a test for how old peers
    degrade is exactly how a fleet rolls into a protocol split. The
    tag table is read by AST, so the lint never imports the native
    codec module.

Suppression: append `# analysis: ok(<reason>)` to the flagged line (or
the line above) — deliberate exceptions stay visible and reasoned at
the site. Pragmas are honored by every lint (for LOCK03, on any edge
of the cycle; for TAG01, on the tag's assignment line in protocol.py).
"""

import ast
import os
import re

PRAGMA_RE = re.compile(r"#\s*analysis:\s*ok\(([^)]*)\)")

_REPO = os.path.normpath(os.path.join(os.path.dirname(__file__), "..", ".."))
_PKG = os.path.join(_REPO, "distributed_plonk_tpu")

# modules whose code is (or stages) traced kernels: the promotion and
# jit-cache lints run here
KERNEL_DIRS = ("backend", "parallel", "runtime")
# modules with cross-thread shared state: the lock lints run here
# (runtime/ added with the fleet fault domain: LivenessTracker state,
# WorkerState task tables, peer-connection caches are all cross-thread;
# obs/ added with the fleet observability plane: the log ring and the
# scraper's latest-snapshot state are cross-thread too; prover/ /
# circuits/ / aggregate.py added with ISSUE 19 — PipelinedProver and
# the aggregation plane run under the pool's threads and had never
# been linted. Entries ending in ".py" are single top-level modules.)
LOCK_DIRS = ("service", "store", "runtime", "obs", "circuits",
             "prover", "aggregate.py")
# modules that record metrics into the shared registry: the OBS01
# glossary lint runs here; LOG01 (structured-log subsystem glossary)
# shares the same scope
OBS_DIRS = ("service", "store", "runtime", "obs", "circuits",
            "prover", "aggregate.py")

# mutating container-method names treated as writes by LOCK01 (calls on
# self.<attr>.<name>(...)); read-only or thread-safe APIs (queue.put,
# event.set) are deliberately absent
_MUTATORS = {"append", "extend", "insert", "remove", "pop", "popitem",
             "clear", "update", "setdefault", "move_to_end", "sort",
             "add", "discard"}


class Finding:
    def __init__(self, path, line, code, message):
        self.path = path
        self.line = line
        self.code = code
        self.message = message

    def __str__(self):
        rel = os.path.relpath(self.path, _REPO)
        return f"{rel}:{self.line}: {self.code}: {self.message}"


def _pragma_lines(src):
    """Line numbers (1-based) carrying an `# analysis: ok(...)` pragma."""
    out = set()
    for i, line in enumerate(src.splitlines(), start=1):
        if PRAGMA_RE.search(line):
            out.add(i)
    return out


def _suppressed(pragmas, line):
    return line in pragmas or (line - 1) in pragmas


def _names_in(node):
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def _self_attr(node):
    """'self.x' -> 'x' (walking through subscripts: self.x[k] -> 'x')."""
    while isinstance(node, ast.Subscript):
        node = node.value
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


# --- JIT01: jit cache keys ----------------------------------------------------

def _is_jit_call(node):
    """`jax.jit(...)` / `jit(...)` call expression."""
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    return ((isinstance(f, ast.Attribute) and f.attr == "jit")
            or (isinstance(f, ast.Name) and f.id == "jit"))


def _has_jit_decorator(fdef):
    for d in fdef.decorator_list:
        if (isinstance(d, ast.Attribute) and d.attr == "jit") \
                or (isinstance(d, ast.Name) and d.id == "jit") \
                or (isinstance(d, ast.Call) and _is_jit_call(d)):
            return True
    return False


def _local_deps(fn):
    """name -> set(names it was computed from), for simple assignments
    directly in `fn`'s body (no control-flow sensitivity — enough to
    track `plain = boundary == "plain"` style derivations)."""
    deps = {}
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name):
            deps[node.targets[0].id] = _names_in(node.value)
    return deps


def _transitive(names, deps, limit=32):
    out = set(names)
    for _ in range(limit):
        grew = False
        for n in list(out):
            for d in deps.get(n, ()):
                if d not in out:
                    out.add(d)
                    grew = True
        if not grew:
            break
    return out


def _closure_free_names(value, fn, jit_defs):
    """Names the cached value's compiled behavior depends on: names in
    jit(...) call arguments, plus — when the value references a local
    function that carries @jit — that function's body free names."""
    names = set()
    for node in ast.walk(value):
        if _is_jit_call(node):
            for arg in node.args + [kw.value for kw in node.keywords]:
                names |= _names_in(arg)
        elif isinstance(node, ast.Name) and node.id in jit_defs:
            names |= jit_defs[node.id]
    return names


def _jit_def_free_names(fdef):
    """Free names of a nested @jit function: names read in its body that
    are not its own params/locals."""
    bound = {a.arg for a in (fdef.args.args + fdef.args.kwonlyargs
                             + fdef.args.posonlyargs)}
    if fdef.args.vararg:
        bound.add(fdef.args.vararg.arg)
    if fdef.args.kwarg:
        bound.add(fdef.args.kwarg.arg)
    for node in ast.walk(fdef):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    bound.add(t.id)
    free = set()
    for node in ast.walk(fdef):
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load) \
                and node.id not in bound:
            free.add(node.id)
    return free


def _lint_jit_cache(tree, path, src, module_names, findings):
    pragmas = _pragma_lines(src)
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        params = {a.arg for a in fn.args.args + fn.args.kwonlyargs
                  + fn.args.posonlyargs}
        deps = _local_deps(fn)
        jit_defs = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.FunctionDef) and node is not fn \
                    and _has_jit_decorator(node):
                jit_defs[node.name] = _jit_def_free_names(node)
            elif isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and _is_jit_call(node.value):
                jit_defs[node.targets[0].id] = _names_in(node.value)
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Subscript)):
                continue
            target = node.targets[0]
            # only cache DICTS survive across calls: self.<x>[key] = ...
            if _self_attr(target) is None:
                continue
            closure = _closure_free_names(node.value, fn, jit_defs)
            if not closure:
                continue  # not a jit-carrying cache write
            # a closure name is key-derived when every ORIGIN of its
            # assignment chain (a name with no recorded local
            # derivation) is the key itself, `self`, or module scope;
            # an origin that is a function PARAMETER outside the key is
            # exactly the hazard: the trace varies with it, the cache
            # key does not
            key_closure = _transitive(_names_in(target.slice), deps)
            hazards = set()
            for n in sorted(closure):
                if n == "self" or n in module_names or n in key_closure:
                    continue
                chain = _transitive({n}, deps)
                origins = {r for r in chain if r not in deps} or {n}
                hazards |= {r for r in origins
                            if r in params and r not in key_closure
                            and r != "self" and r not in module_names}
            hazards = sorted(hazards)
            if hazards and not _suppressed(pragmas, node.lineno):
                findings.append(Finding(
                    path, node.lineno, "JIT01",
                    f"jit cache write keyed on {sorted(_names_in(target.slice))} "
                    f"but the cached trace also depends on {hazards} — a "
                    "call differing only there reuses the wrong compiled "
                    "program (add them to the key or derive them from it)"))


# --- PROM: dtype promotion ----------------------------------------------------

def _lint_promotion(tree, path, src, findings):
    pragmas = _pragma_lines(src)
    for node in ast.walk(tree):
        if isinstance(node, ast.BinOp):
            for side in (node.left, node.right):
                if isinstance(side, ast.Constant) \
                        and isinstance(side.value, float):
                    other = node.right if side is node.left else node.left
                    if isinstance(other, ast.Constant):
                        continue  # constant folding, no array involved
                    if _suppressed(pragmas, node.lineno):
                        continue
                    findings.append(Finding(
                        path, node.lineno, "PROM01",
                        f"float literal {side.value!r} in kernel-module "
                        "arithmetic: jnp silently promotes uint32 "
                        "operands to f32 (use an int, or mark the "
                        "host-only expression with # analysis: ok(...))"))
                    break
        elif isinstance(node, ast.Attribute) and node.attr == "float64":
            if not _suppressed(pragmas, node.lineno):
                findings.append(Finding(
                    path, node.lineno, "PROM02",
                    "float64 reference in a kernel module (the limb "
                    "pipeline is 32-bit end to end)"))


# --- LOCK01: lock discipline --------------------------------------------------

def _lock_attrs(cls):
    """Attrs assigned threading.Lock()/RLock() anywhere in the class."""
    out = set()
    for node in ast.walk(cls):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            f = node.value.func
            name = f.attr if isinstance(f, ast.Attribute) else \
                (f.id if isinstance(f, ast.Name) else None)
            if name in ("Lock", "RLock"):
                for t in node.targets:
                    attr = _self_attr(t)
                    if attr:
                        out.add(attr)
    return out


def _with_lock_ranges(method, locks):
    """(start, end) line ranges of `with self.<lock>` bodies."""
    ranges = []
    for node in ast.walk(method):
        if not isinstance(node, ast.With):
            continue
        for item in node.items:
            attr = _self_attr(item.context_expr)
            if attr in locks:
                end = max(getattr(n, "end_lineno", n.lineno)
                          for n in node.body)
                ranges.append((node.body[0].lineno
                               if node.body else node.lineno, end))
                break
    return ranges


def _flat_targets(targets):
    """Assignment targets with tuple/list unpacking flattened."""
    out = []
    for t in targets:
        if isinstance(t, (ast.Tuple, ast.List)):
            out.extend(_flat_targets(t.elts))
        else:
            out.append(t)
    return out


def _writes_in(method):
    """[(attr, line)] of self-attribute mutations in a method."""
    out = []
    for node in ast.walk(method):
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in _flat_targets(targets):
                attr = _self_attr(t)
                if attr:
                    out.append((attr, node.lineno,
                                isinstance(t, ast.Subscript)))
        elif isinstance(node, (ast.For, ast.AsyncFor)):
            for t in _flat_targets([node.target]):
                attr = _self_attr(t)
                if attr:
                    out.append((attr, node.lineno, False))
        elif isinstance(node, ast.Delete):
            for t in node.targets:
                attr = _self_attr(t)
                if attr:
                    out.append((attr, node.lineno, True))
        elif isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr in _MUTATORS:
            attr = _self_attr(node.func.value)
            if attr:
                out.append((attr, node.lineno, True))
    return out


def _reads_in(method):
    """[(attr, line)] of self-attribute loads in a method."""
    out = []
    for node in ast.walk(method):
        if isinstance(node, ast.Attribute) \
                and isinstance(node.ctx, ast.Load):
            attr = _self_attr(node)
            if attr:
                out.append((attr, node.lineno))
    return out


def _method_calls(method):
    """Names of self.<m>(...) calls made by a method, with lines."""
    out = []
    for node in ast.walk(method):
        if isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and isinstance(node.func.value, ast.Name) \
                and node.func.value.id == "self":
            out.append((node.func.attr, node.lineno))
    return out


def _lint_locks(tree, path, src, findings):
    pragmas = _pragma_lines(src)
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        locks = _lock_attrs(cls)
        if not locks:
            continue
        methods = {m.name: m for m in cls.body
                   if isinstance(m, ast.FunctionDef)}
        ranges = {name: _with_lock_ranges(m, locks)
                  for name, m in methods.items()}

        def _in_lock(name, line):
            return any(a <= line <= b for a, b in ranges.get(name, ()))

        # fixpoint: a method is lock-held if every intra-class call site
        # is inside a lock scope or in a lock-held method (__init__ and
        # the lock-holding frames count as held: single-threaded
        # construction / already-serialized)
        held = {"__init__"}
        callers = {}  # method -> [(caller, line)]
        for name, m in methods.items():
            for callee, line in _method_calls(m):
                callers.setdefault(callee, []).append((name, line))
        changed = True
        while changed:
            changed = False
            for name in methods:
                if name in held or name not in callers:
                    continue
                if all(caller in held or _in_lock(caller, line)
                       for caller, line in callers[name]):
                    held.add(name)
                    changed = True

        locked_writers = {}    # attr -> first locked write line
        locked_readers = {}    # attr -> first locked read line
        unlocked_writers = {}  # attr -> [(method, line)]
        for name, m in methods.items():
            if name == "__init__":
                continue
            for attr, line, _sub in _writes_in(m):
                if attr in locks:
                    continue
                if name in held or _in_lock(name, line):
                    locked_writers.setdefault(attr, line)
                else:
                    unlocked_writers.setdefault(attr, []).append(
                        (name, line))
            for attr, line in _reads_in(m):
                if attr not in locks \
                        and (name in held or _in_lock(name, line)):
                    locked_readers.setdefault(attr, line)

        for attr, sites in unlocked_writers.items():
            if attr in locked_writers:
                code, other = "LOCK01", ("written under `with self.<lock>`"
                                         f" at line {locked_writers[attr]}")
            elif attr in locked_readers:
                code, other = "LOCK02", ("read under `with self.<lock>` at"
                                         f" line {locked_readers[attr]}")
            else:
                continue
            for method, line in sites:
                if _suppressed(pragmas, line):
                    continue
                findings.append(Finding(
                    path, line, code,
                    f"{cls.name}.{attr} is {other} but mutated without "
                    f"the lock in {method}()"))


# --- LOCK03: lock-acquisition-order graph -------------------------------------

# lock-object methods: calls on these never descend into user code, so a
# held call to them is not an acquisition edge
_LOCK_OBJ_METHODS = {"acquire", "release", "locked", "notify", "notify_all",
                     "wait", "wait_for"}

# method names that collide with builtin container/string/IO protocols:
# excluded from cross-class NAME matching (a held `d.get(k)` on a plain
# dict must not edge into every class exposing a locked `get`). A held
# call through one of these names onto a real linted object is the
# lint's known blind spot — such APIs get reviewed manually.
_GENERIC_METHODS = {"get", "put", "pop", "popitem", "keys", "values",
                    "items", "update", "setdefault", "clear", "copy",
                    "append", "extend", "insert", "remove", "sort",
                    "index", "count", "add", "discard", "split", "join",
                    "strip", "format", "encode", "decode", "read",
                    "write", "close", "flush", "readline", "seek",
                    "load", "loads", "dump", "dumps", "send", "recv"}


def _lock_kinds(cls):
    """({attr: 'Lock'|'RLock'|'Condition'}, {alias_attr: lock_attr}) for
    a class: attrs assigned threading.Lock()/RLock()/Condition() anywhere
    in the class body. `Condition(self._lock)` does not mint a new lock —
    acquiring the condition IS acquiring the wrapped lock, so it is
    recorded as an alias."""
    kinds, aliases = {}, {}
    for node in ast.walk(cls):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            f = node.value.func
            name = f.attr if isinstance(f, ast.Attribute) else \
                (f.id if isinstance(f, ast.Name) else None)
            if name not in ("Lock", "RLock", "Condition"):
                continue
            wrapped = _self_attr(node.value.args[0]) \
                if name == "Condition" and node.value.args else None
            for t in node.targets:
                attr = _self_attr(t)
                if not attr:
                    continue
                if wrapped is not None:
                    aliases[attr] = wrapped
                else:
                    kinds[attr] = name
    # an alias of an unknown lock (Condition over a parameter) counts as
    # its own plain lock
    for a, w in list(aliases.items()):
        if w not in kinds:
            del aliases[a]
            kinds[a] = "Condition"
    return kinds, aliases


def _collect_lock_graph(tree, path, src):
    """Per-class acquisition records for LOCK03 from one module. The
    graph itself is assembled globally (cross-file, cross-class) by
    _lock_graph_findings once every module in scope is collected."""
    pragmas = _pragma_lines(src)
    out = []
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        kinds, aliases = _lock_kinds(cls)
        if not kinds:
            continue
        rec = {"name": cls.name, "path": path, "pragmas": pragmas,
               "kinds": kinds, "methods": {}}
        for m in cls.body:
            if not isinstance(m, ast.FunctionDef):
                continue

            def canon(expr_attr):
                return aliases.get(expr_attr, expr_attr)

            ranges = {}  # lock attr -> [(body start, body end)]
            for node in ast.walk(m):
                if not isinstance(node, ast.With) or not node.body:
                    continue
                end = max(getattr(n, "end_lineno", n.lineno)
                          for n in node.body)
                for item in node.items:
                    attr = canon(_self_attr(item.context_expr))
                    if attr in kinds:
                        ranges.setdefault(attr, []).append(
                            (node.body[0].lineno, end))

            def held(line):
                return {a for a, rs in ranges.items()
                        if any(s <= line <= e for s, e in rs)}

            with_edges, held_calls, self_calls, attr_calls = [], [], [], []
            for node in ast.walk(m):
                if isinstance(node, ast.With):
                    h, here = held(node.lineno), []
                    for item in node.items:
                        attr = canon(_self_attr(item.context_expr))
                        if attr not in kinds:
                            continue
                        for prev in sorted(h) + here:
                            with_edges.append((prev, attr, node.lineno))
                        here.append(attr)
                elif isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and node.func.attr not in _LOCK_OBJ_METHODS:
                    is_self = isinstance(node.func.value, ast.Name) \
                        and node.func.value.id == "self"
                    # cross-class candidates are SIMPLE chains only —
                    # `obj.m()` / `self.attr.m()`; a subscripted chain
                    # (`self._table[k].get(...)`) is container traffic,
                    # and name-matching dict/list protocol calls against
                    # class APIs would flood the graph with false edges
                    simple = isinstance(node.func.value,
                                        (ast.Name, ast.Attribute))
                    if is_self:
                        self_calls.append((node.func.attr, node.lineno))
                    elif simple:
                        attr_calls.append((node.func.attr, node.lineno))
                    h = held(node.lineno)
                    if h and (is_self or simple):
                        held_calls.append((node.func.attr, is_self,
                                           frozenset(h), node.lineno))
            rec["methods"][m.name] = {
                "direct": set(ranges), "with_edges": with_edges,
                "held_calls": held_calls, "self_calls": self_calls,
                "attr_calls": attr_calls}
        out.append(rec)
    return out


def _lock_graph_findings(class_infos):
    """Assemble the global may-hold-while-acquiring graph and report one
    LOCK03 finding per cycle (strongly connected component, or self-edge
    on a non-reentrant lock)."""
    # per-class transitive acquires: locks a method may take through its
    # intra-class self-call closure (fixpoint); the same closure carries
    # the method names it calls on OTHER objects, so a helper invoked
    # under a lock still contributes its outbound cross-class calls
    for rec in class_infos:
        methods = rec["methods"]
        trans = {n: set(m["direct"]) for n, m in methods.items()}
        ext = {n: {c for c, _l in m["attr_calls"]}
               for n, m in methods.items()}
        changed = True
        while changed:
            changed = False
            for n, m in methods.items():
                for callee, _line in m["self_calls"]:
                    extra = trans.get(callee, set()) - trans[n]
                    extra_ext = ext.get(callee, set()) - ext[n]
                    if extra or extra_ext:
                        trans[n] |= extra
                        ext[n] |= extra_ext
                        changed = True
        rec["trans"] = trans
        rec["ext"] = ext

    # method-name index for cross-class edges (no type inference: a held
    # call `obj.submit(...)` edges into every linted class whose `submit`
    # may acquire a lock)
    by_method = {}
    for rec in class_infos:
        for mname, acquired in rec["trans"].items():
            if acquired:
                by_method.setdefault(mname, []).append((rec, acquired))

    def name_targets(callee):
        if callee in _GENERIC_METHODS:
            return []
        return [(rec2, lock) for rec2, locks in by_method.get(callee, ())
                for lock in locks]

    edges = {}  # (src, dst) -> (path, line, suppressed)

    def add_edge(src_rec, src_attr, dst_node, line, path, pragmas):
        src = (src_rec["name"], src_attr)
        if src == dst_node \
                and src_rec["kinds"].get(src_attr) == "RLock":
            return  # re-entrant re-acquisition is fine
        key = (src, dst_node)
        if key not in edges:
            edges[key] = (path, line, _suppressed(pragmas, line))

    for rec in class_infos:
        for m in rec["methods"].values():
            for a, b, line in m["with_edges"]:
                add_edge(rec, a, (rec["name"], b), line,
                         rec["path"], rec["pragmas"])
            for callee, is_self, held, line in m["held_calls"]:
                # name matches back into the SAME class are dropped: the
                # receiver is not self (a helper object whose method name
                # collides with the class API — Histogram.snapshot vs
                # Metrics.snapshot), and intra-class edges are already
                # covered precisely by the self./trans path
                if is_self:
                    # everything the callee may acquire: its own class's
                    # locks plus its outbound calls' name matches
                    targets = [(rec["name"], lock)
                               for lock in rec["trans"].get(callee, ())]
                    for name in rec["ext"].get(callee, ()):
                        targets += [(r2["name"], lock)
                                    for r2, lock in name_targets(name)
                                    if r2 is not rec]
                else:
                    targets = [(r2["name"], lock)
                               for r2, lock in name_targets(callee)
                               if r2 is not rec]
                for h in held:
                    for dst in targets:
                        add_edge(rec, h, dst, line,
                                 rec["path"], rec["pragmas"])

    graph = {}
    for (src, dst) in edges:
        graph.setdefault(src, set()).add(dst)
        graph.setdefault(dst, set())

    # Tarjan SCC (graphs here are tiny; recursion depth is bounded by
    # the node count)
    index_of, low, stack, on_stack, sccs = {}, {}, [], set(), []

    def strongconnect(v, counter=[0]):
        index_of[v] = low[v] = counter[0]
        counter[0] += 1
        stack.append(v)
        on_stack.add(v)
        for w in graph.get(v, ()):
            if w not in index_of:
                strongconnect(w)
                low[v] = min(low[v], low[w])
            elif w in on_stack:
                low[v] = min(low[v], index_of[w])
        if low[v] == index_of[v]:
            comp = []
            while True:
                w = stack.pop()
                on_stack.discard(w)
                comp.append(w)
                if w == v:
                    break
            sccs.append(comp)

    for v in graph:
        if v not in index_of:
            strongconnect(v)

    findings = []
    for comp in sccs:
        comp_set = set(comp)
        if len(comp) == 1:
            v = comp[0]
            if (v, v) not in edges:
                continue
            cycle = [v, v]
        else:
            # shortest representative cycle from one node back to itself
            # through the component
            start = min(comp_set)
            prev, frontier, seen = {}, [start], {start}
            cycle = None
            while frontier and cycle is None:
                nxt = []
                for u in frontier:
                    for w in graph.get(u, ()):
                        if w == start:
                            cycle = [start]
                            node = u
                            while node != start:
                                cycle.append(node)
                                node = prev[node]
                            cycle.append(start)
                            cycle.reverse()
                            break
                        if w in comp_set and w not in seen:
                            seen.add(w)
                            prev[w] = u
                            nxt.append(w)
                    if cycle:
                        break
                frontier = nxt
            if cycle is None:
                continue  # unreachable for a true SCC
        sites = [edges[(cycle[i], cycle[i + 1])]
                 for i in range(len(cycle) - 1)]
        if any(sup for _p, _l, sup in sites):
            continue  # a pragma on any edge breaks the cycle
        names = " -> ".join(f"{c}.{a}" for c, a in cycle)
        where = "; ".join(f"{os.path.relpath(p, _REPO)}:{line}"
                          for p, line, _s in sites)
        path, line, _s = sites[0]
        if len(cycle) == 2 and cycle[0] == cycle[1]:
            msg = (f"non-reentrant lock {names.split(' -> ')[0]} may be "
                   f"re-acquired while already held (self-deadlock); "
                   f"acquisition sites: {where}")
        else:
            msg = (f"lock-order cycle {names}: two threads taking these "
                   f"locks in opposite orders deadlock; acquisition "
                   f"sites: {where}")
        findings.append(Finding(path, line, "LOCK03", msg))
    return findings


# --- OBS01: metric-name glossary ----------------------------------------------

_GLOSSARY_PATH = os.path.join(_PKG, "service", "metrics.py")
_GLOSSARY_TOKEN_RE = re.compile(r"[a-z][a-z0-9_/]*(?:\*)?")


def parse_glossary(doc):
    """(exact names, wildcard prefixes) from a glossary docstring. Only
    the NAME COLUMN of indented entry lines is read — the entry format
    is `    name [/ name...]  description`, names separated from the
    description by >= 2 spaces — so prose (descriptions, paragraphs)
    can't accidentally document a metric; a token `family_*` (or
    `family/*`) documents every name under that prefix."""
    exact, prefixes = set(), []
    for line in doc.splitlines():
        if not line.startswith("    ") or not line.strip():
            continue
        name_col = re.split(r"\s{2,}", line.strip(), maxsplit=1)[0]
        for tok in _GLOSSARY_TOKEN_RE.findall(name_col):
            if tok.endswith("*"):
                prefixes.append(tok[:-1])
            else:
                exact.add(tok)
    return exact, tuple(prefixes)


def _load_glossary():
    with open(_GLOSSARY_PATH) as f:
        tree = ast.parse(f.read(), filename=_GLOSSARY_PATH)
    return parse_glossary(ast.get_docstring(tree) or "")


def _documented(name, glossary):
    exact, prefixes = glossary
    for n in (name, "store_" + name):  # scoped-registry publication
        if n in exact or any(n.startswith(p) for p in prefixes):
            return True
    return False


def _lint_obs(tree, path, src, findings, glossary):
    pragmas = _pragma_lines(src)
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("inc", "observe")
                and node.args
                and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            continue
        name = node.args[0].value
        if _documented(name, glossary) or _suppressed(pragmas, node.lineno):
            continue
        findings.append(Finding(
            path, node.lineno, "OBS01",
            f"metric {name!r} is recorded here but absent from the "
            "service/metrics.py glossary — document it (or a matching "
            "`family_*` wildcard) so the /metrics line stays legible"))


# --- LOG01: structured-log subsystem glossary ---------------------------------

_LOG_GLOSSARY_PATH = os.path.join(_PKG, "obs", "log.py")


def parse_log_glossary(doc):
    """Documented subsystem names from a glossary docstring — delegates
    to obs/log.py's canonical parser (stdlib-only import), so the
    vocabulary this lint enforces and log.documented_subsystems() are
    the product of ONE parser."""
    from ..obs.log import parse_subsystem_glossary
    return parse_subsystem_glossary(doc)


def _load_log_glossary():
    with open(_LOG_GLOSSARY_PATH) as f:
        tree = ast.parse(f.read(), filename=_LOG_GLOSSARY_PATH)
    return parse_log_glossary(ast.get_docstring(tree) or "")


def _lint_log_subsystems(tree, path, src, findings, subsystems):
    pragmas = _pragma_lines(src)
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = f.attr if isinstance(f, ast.Attribute) else \
            (f.id if isinstance(f, ast.Name) else None)
        if name != "emit":
            continue
        if not (node.args and isinstance(node.args[0], ast.Constant)
                and isinstance(node.args[0].value, str)):
            continue
        sub = node.args[0].value
        if sub in subsystems or _suppressed(pragmas, node.lineno):
            continue
        findings.append(Finding(
            path, node.lineno, "LOG01",
            f"log subsystem {sub!r} is emitted here but absent from the "
            "obs/log.py subsystem glossary — document it so the fleet's "
            "structured logs keep one vocabulary"))


# --- ENV01: DPT_* knob glossary -----------------------------------------------

_KNOB_GLOSSARY_PATH = os.path.join(_PKG, "constants.py")
_KNOB_RE = re.compile(r"DPT_[A-Z0-9_]+")
_KNOB_TOKEN_RE = re.compile(r"DPT_[A-Z0-9_]*\*?")


def parse_knob_glossary(doc):
    """(exact names, wildcard prefixes) from the knob glossary held in a
    module docstring — same shape as the OBS01 metric glossary: only the
    NAME COLUMN of indented lines is read (name separated from the
    description by >= 2 spaces), and a `DPT_FAMILY_*` token documents
    every knob under that prefix."""
    exact, prefixes = set(), []
    for line in doc.splitlines():
        if not line.startswith("    ") or not line.strip():
            continue
        name_col = re.split(r"\s{2,}", line.strip(), maxsplit=1)[0]
        for tok in _KNOB_TOKEN_RE.findall(name_col):
            if tok.endswith("*"):
                prefixes.append(tok[:-1])
            else:
                exact.add(tok)
    return exact, tuple(prefixes)


def _load_knob_glossary():
    with open(_KNOB_GLOSSARY_PATH) as f:
        tree = ast.parse(f.read(), filename=_KNOB_GLOSSARY_PATH)
    return parse_knob_glossary(ast.get_docstring(tree) or "")


def _knob_documented(name, glossary):
    exact, prefixes = glossary
    return name in exact or any(name.startswith(p) for p in prefixes)


def _lint_env_knobs(tree, path, src, findings, glossary):
    """Every standalone string literal naming a DPT_* knob (env reads,
    helper-wrapped reads, registry patch targets) must be documented.
    Only whole-literal matches count, so prose mentioning a knob inside
    a docstring or message never false-passes OR false-fails."""
    pragmas = _pragma_lines(src)
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Constant)
                and isinstance(node.value, str)
                and _KNOB_RE.fullmatch(node.value)):
            continue
        if _knob_documented(node.value, glossary) \
                or _suppressed(pragmas, node.lineno):
            continue
        findings.append(Finding(
            path, node.lineno, "ENV01",
            f"knob {node.value!r} is read here but absent from the "
            "constants.py knob glossary — document it (or a matching "
            "`DPT_FAMILY_*` wildcard) so operators can discover it"))


# --- TAG01: wire-tag conformance ----------------------------------------------

_PROTOCOL_PATH = os.path.join(_PKG, "runtime", "protocol.py")
_TESTS_DIR = os.path.join(_REPO, "tests")
# mirrors protocol.py's TAG_NAMES comprehension (non-tag uppercase ints)
_NON_TAG_CONSTS = ("FR_BYTES", "FQ_BYTES", "POINT_BYTES")


def _protocol_tags():
    """{tag name: assignment line}, replicated from protocol.TAG_NAMES'
    comprehension by AST so the lint never imports the native codec."""
    with open(_PROTOCOL_PATH) as f:
        tree = ast.parse(f.read(), filename=_PROTOCOL_PATH)
    consts = {}
    for node in tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and node.targets[0].id.isupper() \
                and isinstance(node.value, ast.Constant) \
                and isinstance(node.value.value, int):
            consts[node.targets[0].id] = (node.value.value, node.lineno)
    err = consts.get("ERR", (101, 0))[0]
    return {name: line for name, (value, line) in consts.items()
            if 0 < value <= err and name not in _NON_TAG_CONSTS}


def _tag_refs_in(tree, tags):
    """Tag names referenced by this module (protocol.NAME attribute
    access or a bare NAME from-import use)."""
    refs = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Attribute) and node.attr in tags:
            refs.add(node.attr)
        elif isinstance(node, ast.Name) and node.id in tags:
            refs.add(node.id)
    return refs


def _tag_findings(tags, code_refs):
    """TAG01 findings: tags with no package encode/decode site or no
    test reference. `code_refs` = tag names seen in package code outside
    protocol.py."""
    with open(_PROTOCOL_PATH) as f:
        src = f.read()
    pragmas = _pragma_lines(src)
    test_blob = []
    if os.path.isdir(_TESTS_DIR):
        for fname in sorted(os.listdir(_TESTS_DIR)):
            if fname.endswith(".py"):
                with open(os.path.join(_TESTS_DIR, fname)) as f:
                    test_blob.append(f.read())
    test_blob = "\n".join(test_blob)
    findings = []
    for name, line in sorted(tags.items(), key=lambda kv: kv[1]):
        if _suppressed(pragmas, line):
            continue
        missing = []
        if name not in code_refs:
            missing.append("encode/decode site in the package")
        if not re.search(rf"\b{name}\b", test_blob):
            missing.append("back-compat test reference under tests/")
        if missing:
            findings.append(Finding(
                _PROTOCOL_PATH, line, "TAG01",
                f"wire tag {name} has no {' and no '.join(missing)} — "
                "every protocol tag needs a live codec site and an "
                "old-peer degradation test before it ships"))
    return findings


# --- driver -------------------------------------------------------------------

def _module_globals(tree):
    names = set()
    for node in tree.body:
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
        elif isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            names.add(node.name)
        elif isinstance(node, ast.Import):
            for a in node.names:
                names.add((a.asname or a.name).split(".")[0])
        elif isinstance(node, ast.ImportFrom):
            for a in node.names:
                names.add(a.asname or a.name)
    return names


def _iter_py(root, subdirs):
    """Yield .py files under each subdir; an entry ending in ".py" is a
    single top-level module (aggregate.py)."""
    for sub in subdirs:
        d = os.path.join(root, sub)
        if sub.endswith(".py"):
            if os.path.isfile(d):
                yield d
            continue
        if not os.path.isdir(d):
            continue
        for fname in sorted(os.listdir(d)):
            if fname.endswith(".py"):
                yield os.path.join(d, fname)


def _iter_py_all(root):
    """Every .py file in the package (the ENV01/TAG01 scope)."""
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for fname in sorted(filenames):
            if fname.endswith(".py"):
                yield os.path.join(dirpath, fname)


def run_lints(pkg_root=_PKG):
    """All lints over their target scopes. Returns [Finding]."""
    findings = []
    glossary = _load_glossary()
    log_glossary = _load_log_glossary()
    knob_glossary = _load_knob_glossary()
    tags = _protocol_tags()
    scoped = set(_iter_py(pkg_root, KERNEL_DIRS + LOCK_DIRS + OBS_DIRS))
    lock_classes, tag_refs = [], set()
    for path in _iter_py_all(pkg_root):
        with open(path) as f:
            src = f.read()
        tree = ast.parse(src, filename=path)
        # package-wide scopes: knob glossary + tag reference collection
        _lint_env_knobs(tree, path, src, findings, knob_glossary)
        if os.path.normpath(path) != os.path.normpath(_PROTOCOL_PATH):
            tag_refs |= _tag_refs_in(tree, tags)
        if path not in scoped:
            continue
        rel = os.path.relpath(path, pkg_root)
        top = rel.split(os.sep)[0]
        if top in KERNEL_DIRS:
            _lint_jit_cache(tree, path, src, _module_globals(tree),
                            findings)
            _lint_promotion(tree, path, src, findings)
        if top in LOCK_DIRS:
            _lint_locks(tree, path, src, findings)
            lock_classes += _collect_lock_graph(tree, path, src)
        if top in OBS_DIRS:
            _lint_obs(tree, path, src, findings, glossary)
            _lint_log_subsystems(tree, path, src, findings, log_glossary)
    findings += _lock_graph_findings(lock_classes)
    findings += _tag_findings(tags, tag_refs)
    return findings


def lint_source(src, path="<string>", kinds=("jit", "prom", "lock"),
                glossary_doc=None, log_glossary_doc=None,
                knob_glossary_doc=None):
    """Lint one source string (unit tests / editor integration).
    glossary_doc: docstring text for the "obs" kind (defaults to the
    real service/metrics.py glossary); log_glossary_doc likewise for
    the "log" kind (defaults to the real obs/log.py glossary);
    knob_glossary_doc likewise for the "env" kind (defaults to the real
    constants.py knob glossary). The "lock" kind runs LOCK01/LOCK02 and
    the LOCK03 order graph over the classes in this one source string."""
    findings = []
    tree = ast.parse(src, filename=path)
    if "jit" in kinds:
        _lint_jit_cache(tree, path, src, _module_globals(tree), findings)
    if "prom" in kinds:
        _lint_promotion(tree, path, src, findings)
    if "lock" in kinds:
        _lint_locks(tree, path, src, findings)
        findings += _lock_graph_findings(
            _collect_lock_graph(tree, path, src))
    if "obs" in kinds:
        glossary = parse_glossary(glossary_doc) \
            if glossary_doc is not None else _load_glossary()
        _lint_obs(tree, path, src, findings, glossary)
    if "log" in kinds:
        subsystems = parse_log_glossary(log_glossary_doc) \
            if log_glossary_doc is not None else _load_log_glossary()
        _lint_log_subsystems(tree, path, src, findings, subsystems)
    if "env" in kinds:
        knobs = parse_knob_glossary(knob_glossary_doc) \
            if knob_glossary_doc is not None else _load_knob_glossary()
        _lint_env_knobs(tree, path, src, findings, knobs)
    return findings
