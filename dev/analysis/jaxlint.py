"""jaxlint — AST + lightweight-dataflow analyzer for TPU footguns.

Dependency-free (stdlib ``ast`` only; never imports jax), so it can run
in any environment, including the dev harness and CI containers without
accelerator runtimes. ``dev/lint.py`` is the entry point and delegates
here.

Rules (see docs/STATIC_ANALYSIS.md for the failure modes on TPU):

- JX1  host sync on a device value: ``float()``/``int()``/``bool()``/
       ``.item()``/``.tolist()``/``np.asarray()`` applied to a traced or
       jax-derived value inside a jit-compiled (or jit-reachable)
       function — a trace-time concretization bug — or inside a loop
       body in library code — a per-iteration device→host transfer that
       serializes dispatch. ``jax.device_get`` is the sanctioned idiom
       for an explicit, batched readback and is never flagged.
- JX2  PRNG key reuse: the same key variable consumed by two
       ``jax.random.*`` calls without an intervening rebind from
       ``split``/``fold_in``/``PRNGKey``.
- JX3  use-after-donation: a variable read after being passed in a
       ``donate_argnums`` position of a jitted callable without being
       rebound first (donated buffers may already be aliased/freed).
- JX4  collective axis-name mismatch: a string axis name in a
       ``lax.psum``-family call that no mesh/pmap/PartitionSpec literal
       in the same file binds.
- JX5  module-level jax import in a host-only package (configurable
       prefix list; the observability subsystem's old OBS1 contract).

Suppression: append ``# jaxlint: disable=JX1`` (comma-separate several
ids; bare ``disable`` silences every rule) to the finding's line.

Baseline: ``dev/analysis/baseline.txt`` grandfathers pre-existing
findings by ``path:RULE:stripped-source-line`` fingerprint so the
repo-wide self-check runs clean while the debt is burned down; stale
entries (matching nothing) are themselves reported so the file only
ever shrinks.
"""
from __future__ import annotations

import ast
import os
import re

__all__ = [
    "Finding", "RULES", "analyze_source", "analyze_file", "run",
    "load_baseline", "apply_baseline", "format_baseline_entry",
    "BASELINE_PATH", "HOST_ONLY_PREFIXES", "LOOP_SYNC_PREFIXES",
]

RULES = {
    "JX1": "host sync on a device value (jit or per-iteration loop)",
    "JX2": "PRNG key reused without an intervening split",
    "JX3": "variable read after donation to a jitted call",
    "JX4": "collective axis name bound by no mesh/pmap in this file",
    "JX5": "module-level jax import in a host-only package",
}

BASELINE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "baseline.txt")

# packages that must stay importable without jax (host-only contract);
# extend as new host-only subsystems appear. dataset/prefetch.py: the
# input pipeline's queue/thread machinery is host-only — its sanctioned
# placement calls (device_put / make_array_from_process_local_data)
# lazy-import jax inside the functions that issue them.
# serving/: the router/pool/prefix-cache plane is host orchestration
# over the batcher API — device work stays inside the batchers it
# drives (the ContinuousBatcher class itself is lazy-imported).
# tuning/: records/search/cache bookkeeping is host-side; the
# measurement and lower/compile/serialize calls lazy-import jax inside
# the functions that issue them
# elastic/: manifests, the checkpoint writer thread, and the restart
# runner are host machinery (the runner must not even initialize a
# backend); snapshot/placement calls lazy-import jax where issued
# deploy/: the weight publisher / canary control plane is host
# orchestration over the replica API — checkpoint loading and the
# quantize round-trip lazy-import jax inside the functions that issue
# them
HOST_ONLY_PREFIXES = ("bigdl_tpu/observability/",
                      "bigdl_tpu/dataset/prefetch.py",
                      "bigdl_tpu/dataset/recordstore.py",
                      "bigdl_tpu/dataset/distributed.py",
                      "bigdl_tpu/serving/",
                      "bigdl_tpu/tuning/",
                      "bigdl_tpu/elastic/",
                      "bigdl_tpu/deploy/")

# the per-iteration-sync flavor of JX1 only applies to library code:
# tests and dev tooling are host drivers that sync deliberately
LOOP_SYNC_PREFIXES = ("bigdl_tpu/",)

_JIT_WRAPPERS = {"jax.jit", "jax.pjit", "jax.experimental.pjit.pjit"}
# transforms that trace the function passed to them: host syncs inside
# are concretization errors exactly like under jit
_TRACED_WRAPPERS = _JIT_WRAPPERS | {
    "jax.grad", "jax.value_and_grad", "jax.vmap", "jax.pmap",
    "jax.checkpoint", "jax.remat", "jax.lax.scan", "jax.lax.cond",
    "jax.lax.while_loop", "jax.lax.fori_loop", "jax.lax.map",
    "jax.shard_map", "jax.experimental.shard_map.shard_map"}
_KEY_PRODUCERS = {"jax.random.PRNGKey", "jax.random.key",
                  "jax.random.split", "jax.random.fold_in",
                  "jax.random.wrap_key_data", "jax.random.clone"}
# jax.random functions whose first arg is not a consumed key; fold_in
# derives a fresh key from (key, data) and is the sanctioned way to
# reuse a key across loop iterations, so it does not count as a use
_NON_CONSUMERS = {"jax.random.PRNGKey", "jax.random.key",
                  "jax.random.key_data", "jax.random.wrap_key_data",
                  "jax.random.fold_in", "jax.random.clone"}
_COLLECTIVES = {"jax.lax.psum", "jax.lax.pmean", "jax.lax.pmax",
                "jax.lax.pmin", "jax.lax.all_gather",
                "jax.lax.all_to_all", "jax.lax.ppermute",
                "jax.lax.pshuffle", "jax.lax.psum_scatter",
                "jax.lax.axis_index"}
_SYNC_BUILTINS = {"float", "int", "bool", "complex"}
_SYNC_METHODS = {"item", "tolist", "__array__"}
_SYNC_NUMPY = {"numpy.asarray", "numpy.array", "numpy.float32",
               "numpy.float64", "numpy.int32", "numpy.int64"}
# attribute reads on a traced value that stay host-side (static)
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "sharding",
                 "aval", "weak_type"}
# builtins whose result is host data even when fed device values
_HOST_BUILTINS = {"len", "range", "enumerate", "isinstance", "getattr",
                  "hasattr", "type", "repr", "str", "id", "zip"}

_SUPPRESS_RE = re.compile(
    r"#\s*jaxlint:\s*disable(?:=([A-Za-z0-9, ]+))?")


class Finding:
    """One analyzer finding, ordered and printable like flake8."""

    __slots__ = ("path", "line", "rule", "msg", "source")

    def __init__(self, path, line, rule, msg, source=""):
        self.path = path
        self.line = line
        self.rule = rule
        self.msg = msg
        self.source = source        # stripped source text of the line

    def key(self):
        return (self.path, self.line, self.rule, self.msg)

    def fingerprint(self):
        return (self.path, self.rule, self.source)

    def __repr__(self):
        return f"{self.path}:{self.line}: {self.rule} {self.msg}"


def _qualname(node, aliases):
    """Resolve a Name/Attribute chain to a dotted name, mapping the
    root through the module's import aliases (``jnp.max`` →
    ``jax.numpy.max``). Returns None for non-name roots (calls,
    subscripts)."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    parts.reverse()
    root = parts[0]
    if root in aliases:
        return ".".join([aliases[root]] + parts[1:])
    return ".".join(parts)


def _collect_aliases(tree):
    """alias -> dotted module/object path, from every import in the
    file (function-local lazy imports included — they resolve the same
    names)."""
    aliases = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname:
                    aliases[a.asname] = a.name
                else:
                    aliases[a.name.split(".")[0]] = a.name.split(".")[0]
        elif isinstance(node, ast.ImportFrom) and node.module \
                and node.level == 0:
            for a in node.names:
                if a.name == "*":
                    continue
                aliases[a.asname or a.name] = f"{node.module}.{a.name}"
    return aliases


def _const_strs(node):
    """String constants in a literal (str, or tuple/list of str)."""
    out = []
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        out.append(node.value)
    elif isinstance(node, (ast.Tuple, ast.List)):
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, str):
                out.append(e.value)
    return out


def _donate_positions(call):
    """donate_argnums positions from a jax.jit(...) call node."""
    for kw in call.keywords:
        if kw.arg == "donate_argnums":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                return (v.value,)
            if isinstance(v, (ast.Tuple, ast.List)):
                return tuple(e.value for e in v.elts
                             if isinstance(e, ast.Constant)
                             and isinstance(e.value, int))
    return ()


def _dotted_target(node):
    """A simple Name or one-or-more-level Attribute path as a string
    ('params', 'cache.kp'); None for anything fancier."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


def _own_walk(node):
    """Walk ``node``'s subtree without descending into nested function
    or class definitions — the statements the scope itself executes."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        n = stack.pop()
        yield n
        if not isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.ClassDef)):
            stack.extend(ast.iter_child_nodes(n))


class _Module:
    """Per-file analysis context: parse once, run every pass."""

    def __init__(self, src, rel_path):
        self.src = src
        self.rel = rel_path.replace(os.sep, "/")
        self.lines = src.splitlines()
        self.tree = ast.parse(src)
        self.aliases = _collect_aliases(self.tree)
        self.findings = {}          # key() -> Finding
        self.suppress = self._suppressions()
        self.defs = {}              # name -> [FunctionDef]
        self.def_scope = {}         # id(def) -> (path incl self, in_cls)
        self._collect_defs(self.tree, (), False)
        self.jitted = set()         # id() of jit-compiled defs
        self.donators = {}          # callable name -> donated positions
        self.jax_local_fns = set()  # local defs whose bodies touch jax
        self._index_jit()

    def _collect_defs(self, node, path, in_class):
        """Record every def with its lexical scope path so bare-name
        references resolve like Python does (same-name methods on
        unrelated classes must not alias a jitted local helper)."""
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef,
                                  ast.AsyncFunctionDef)):
                self.defs.setdefault(child.name, []).append(child)
                self.def_scope[id(child)] = (path + (id(child),),
                                             in_class)
                self._collect_defs(child, path + (id(child),), False)
            elif isinstance(child, ast.ClassDef):
                self._collect_defs(child, path, True)
            else:
                self._collect_defs(child, path, in_class)

    def resolve(self, name, scope):
        """Defs a bare ``name`` can refer to from ``scope`` (a tuple of
        enclosing def ids, innermost last): visible iff defined at
        module level or in an enclosing function — never a class
        method — preferring the innermost match."""
        best, best_len = [], -1
        for cand in self.defs.get(name, ()):
            path, in_class = self.def_scope[id(cand)]
            if in_class:
                continue
            parent = path[:-1]
            if parent != scope[:len(parent)]:
                continue
            if len(parent) > best_len:
                best, best_len = [cand], len(parent)
            elif len(parent) == best_len:
                best.append(cand)
        return best

    # -- shared infrastructure -------------------------------------

    def _suppressions(self):
        sup = {}
        for i, line in enumerate(self.lines, 1):
            m = _SUPPRESS_RE.search(line)
            if m:
                ids = m.group(1)
                sup[i] = (frozenset(x.strip().upper()
                                    for x in ids.split(",") if x.strip())
                          if ids else frozenset())
        return sup

    def emit(self, node_or_line, rule, msg):
        line = (node_or_line if isinstance(node_or_line, int)
                else getattr(node_or_line, "lineno", 0))
        sup = self.suppress.get(line)
        if sup is not None and (not sup or rule in sup):
            return
        text = (self.lines[line - 1].strip()
                if 0 < line <= len(self.lines) else "")
        f = Finding(self.rel, line, rule, msg, text)
        self.findings.setdefault(f.key(), f)

    def qual(self, node):
        return _qualname(node, self.aliases)

    def _is_jax_qual(self, q):
        return q is not None and (q == "jax" or q.startswith("jax."))

    def _index_jit(self):
        """Find jit-compiled defs (decorators + jax.jit(f) references),
        donating callables, and jax-touching local functions; close the
        in-module call graph so helpers called from jitted code count
        as jit context too."""
        for fns in self.defs.values():
            for fn in fns:
                for node in ast.walk(fn):
                    q = self.qual(node) if isinstance(
                        node, (ast.Name, ast.Attribute)) else None
                    if self._is_jax_qual(q):
                        self.jax_local_fns.add(fn.name)
                        break
        for fns in self.defs.values():
            for fn in fns:
                for dec in fn.decorator_list:
                    q = self.qual(dec)
                    if q in _JIT_WRAPPERS:
                        self.jitted.add(id(fn))
                    elif isinstance(dec, ast.Call):
                        qf = self.qual(dec.func)
                        if qf in _JIT_WRAPPERS:
                            self.jitted.add(id(fn))
                            pos = _donate_positions(dec)
                            if pos:
                                self.donators[fn.name] = pos
                        elif qf == "functools.partial" and dec.args and \
                                self.qual(dec.args[0]) in _JIT_WRAPPERS:
                            self.jitted.add(id(fn))
                            pos = _donate_positions(dec)
                            if pos:
                                self.donators[fn.name] = pos
        owners = [(self.tree, ())]
        for fns in self.defs.values():
            for fn in fns:
                owners.append((fn, self.def_scope[id(fn)][0]))
        for owner, scope in owners:
            for node in _own_walk(owner):
                if not isinstance(node, ast.Call):
                    continue
                if self.qual(node.func) not in _TRACED_WRAPPERS:
                    continue
                for arg in node.args:
                    if isinstance(arg, ast.Name):
                        for fn in self.resolve(arg.id, scope):
                            self.jitted.add(id(fn))
        # close over in-module calls from jitted functions, and over
        # defs nested inside them (they execute during tracing)
        changed = True
        while changed:
            changed = False
            for owner, scope in owners:
                if id(owner) not in self.jitted:
                    continue
                new = []
                for node in _own_walk(owner):
                    if isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                        new.append(node)
                    elif isinstance(node, ast.Call) and \
                            isinstance(node.func, ast.Name):
                        new.extend(self.resolve(node.func.id, scope))
                for callee in new:
                    if id(callee) not in self.jitted:
                        self.jitted.add(id(callee))
                        changed = True

    def jit_binding(self, value):
        """If ``value`` (an Assign RHS) builds a donating jitted
        callable — ``jax.jit(f, donate_argnums=...)`` optionally chased
        through ``.lower(...).compile()`` — return its donated
        positions, else None."""
        for node in ast.walk(value):
            if isinstance(node, ast.Call) and \
                    self.qual(node.func) in _JIT_WRAPPERS:
                pos = _donate_positions(node)
                if pos:
                    return pos
        return None

    # -- rule drivers ----------------------------------------------

    def analyze(self, *, host_only_prefixes=HOST_ONLY_PREFIXES,
                loop_sync_prefixes=LOOP_SYNC_PREFIXES):
        loop_sync = self.rel.startswith(tuple(loop_sync_prefixes))
        for fns in self.defs.values():
            for fn in fns:
                in_jit = id(fn) in self.jitted
                _SyncWalker(self, in_jit, loop_sync).run(fn)
                _KeyWalker(self).run(fn)
                _DonationWalker(self).run(fn)
        # module-level statements as a pseudo-function
        mod = ast.Module(body=[s for s in self.tree.body
                               if not isinstance(
                                   s, (ast.FunctionDef,
                                       ast.AsyncFunctionDef,
                                       ast.ClassDef))],
                         type_ignores=[])
        _SyncWalker(self, False, loop_sync).run(mod)
        _KeyWalker(self).run(mod)
        _DonationWalker(self).run(mod)
        # class bodies: methods were collected via self.defs already
        self._axis_names()
        if self.rel.startswith(tuple(host_only_prefixes)):
            self._host_only_imports()
        return sorted(self.findings.values(),
                      key=lambda f: (f.path, f.line, f.rule))

    def _axis_names(self):
        """JX4: literal collective axis names vs axis names bound by
        any mesh/pmap/PartitionSpec literal in this file."""
        bound = set()
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            q = self.qual(node.func) or ""
            base = q.rsplit(".", 1)[-1]
            if base in ("Mesh", "make_mesh", "AbstractMesh"):
                if len(node.args) > 1:
                    bound.update(_const_strs(node.args[1]))
            elif base == "PartitionSpec":
                for a in node.args:
                    bound.update(_const_strs(a))
            if q in _COLLECTIVES:
                continue   # a collective's own axis_name binds nothing
            for kw in node.keywords:
                if kw.arg in ("axis_name", "axis_names"):
                    bound.update(_const_strs(kw.value))
        if not bound:
            return     # file declares no axes: nothing to check against
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            q = self.qual(node.func)
            if q not in _COLLECTIVES:
                continue
            axis_pos = 0 if q == "jax.lax.axis_index" else 1
            axis_arg = None
            if len(node.args) > axis_pos:
                axis_arg = node.args[axis_pos]
            for kw in node.keywords:
                if kw.arg == "axis_name":
                    axis_arg = kw.value
            if axis_arg is None:
                continue
            for name in _const_strs(axis_arg):
                if name not in bound:
                    self.emit(
                        node, "JX4",
                        f"collective axis name '{name}' is bound by no "
                        f"mesh/pmap in this file (known: "
                        f"{sorted(bound)})")

    def _host_only_imports(self):
        """JX5: module-scope jax imports in host-only packages.
        Function-local imports stay legal — lazy loads don't couple
        module import to the device runtime."""
        for node in self.tree.body:
            mods = []
            if isinstance(node, ast.Import):
                mods = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                mods = [node.module or ""]
            for m in mods:
                if m == "jax" or m.startswith("jax."):
                    self.emit(node, "JX5",
                              "module-level jax import in host-only "
                              "package (lazy-import inside the function "
                              "that needs it)")


class _FlowWalker:
    """Order-aware statement walker shared by the dataflow rules.

    Visits a function body in execution order; loop bodies are visited
    twice so state carried across an iteration (a key consumed, a
    buffer donated) is observed by the loop's own reads. If/else
    branches run against a snapshot and merge. Nested function defs
    are walked by the module driver separately — here they only
    contribute their names."""

    def __init__(self, mod):
        self.mod = mod
        self.loop_depth = 0

    def run(self, fn):
        if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            self.enter_function(fn)
        self.block(fn.body)

    def enter_function(self, fn):
        pass

    def block(self, stmts):
        for s in stmts:
            self.stmt(s)

    def stmt(self, s):
        if isinstance(s, (ast.For, ast.AsyncFor)):
            self.expr(s.iter)
            self.assign_target(s.target, s.iter)
            self.loop_depth += 1
            self.block(s.body)
            self.block(s.body)
            self.loop_depth -= 1
            self.block(s.orelse)
        elif isinstance(s, ast.While):
            self.loop_depth += 1
            self.expr(s.test)
            self.block(s.body)
            self.expr(s.test)
            self.block(s.body)
            self.loop_depth -= 1
            self.block(s.orelse)
        elif isinstance(s, ast.If):
            self.expr(s.test)
            before = self.snapshot()
            self.block(s.body)
            after_body = self.snapshot()
            self.restore(before)
            self.block(s.orelse)
            self.merge(after_body)
        elif isinstance(s, ast.Try):
            self.block(s.body)
            for h in s.handlers:
                self.block(h.body)
            self.block(s.orelse)
            self.block(s.finalbody)
        elif isinstance(s, (ast.With, ast.AsyncWith)):
            for item in s.items:
                self.expr(item.context_expr)
                if item.optional_vars is not None:
                    self.assign_target(item.optional_vars, None)
            self.block(s.body)
        elif isinstance(s, ast.Assign):
            self.expr(s.value)
            for t in s.targets:
                self.assign_target(t, s.value)
        elif isinstance(s, ast.AnnAssign):
            if s.value is not None:
                self.expr(s.value)
                self.assign_target(s.target, s.value)
        elif isinstance(s, ast.AugAssign):
            self.expr(s.value)
            self.assign_target(s.target, s.value)
        elif isinstance(s, ast.Expr):
            self.expr(s.value)
        elif isinstance(s, ast.Return):
            self.expr(s.value)
        elif isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.ClassDef)):
            pass          # nested scopes analyzed by the module driver
        else:
            for child in ast.iter_child_nodes(s):
                if isinstance(child, ast.expr):
                    self.expr(child)
                elif isinstance(child, ast.stmt):
                    self.stmt(child)

    def expr(self, e):
        """Post-order walk of an expression, calling ``on_call`` after
        a call's arguments were visited (so donation applies after the
        args were read) and ``on_load`` for every Name/Attribute
        read."""
        if e is None:
            return
        for child in ast.iter_child_nodes(e):
            if isinstance(child, ast.expr):
                self.expr(child)
            elif isinstance(child, ast.stmt):   # lambda bodies etc.
                self.stmt(child)
            elif isinstance(child, (ast.comprehension,)):
                self.expr(child.iter)
                for c in child.ifs:
                    self.expr(c)
        if isinstance(e, ast.Call):
            self.on_call(e)
        elif isinstance(e, (ast.Name, ast.Attribute)) and \
                isinstance(getattr(e, "ctx", None), ast.Load):
            self.on_load(e)

    # hooks -----------------------------------------------------------
    def on_call(self, call):
        pass

    def on_load(self, node):
        pass

    def assign_target(self, target, value):
        pass

    def snapshot(self):
        return None

    def restore(self, state):
        pass

    def merge(self, other):
        pass


def _bound_names(target):
    """Names an assignment target BINDS. ``obj.attr = v`` and
    ``obj[i] = v`` bind nothing: they neither make ``obj`` a device
    value nor clear it."""
    if isinstance(target, ast.Name):
        yield target.id
    elif isinstance(target, (ast.Tuple, ast.List)):
        for elt in target.elts:
            yield from _bound_names(elt)
    elif isinstance(target, ast.Starred):
        yield from _bound_names(target.value)


class _SyncWalker(_FlowWalker):
    """JX1 — host syncs on device values.

    Tracks which local names hold device values: parameters of jitted
    functions, results of jax-rooted calls (``jnp.*``/``lax.*``/...),
    results of in-module functions whose bodies touch jax, and
    anything derived from those by assignment."""

    def __init__(self, mod, in_jit, loop_sync):
        super().__init__(mod)
        self.in_jit = in_jit
        self.loop_sync = loop_sync
        self.device = set()

    def enter_function(self, fn):
        if self.in_jit:
            a = fn.args
            for arg in (a.posonlyargs + a.args + a.kwonlyargs):
                self.device.add(arg.arg)
            if a.vararg:
                self.device.add(a.vararg.arg)

    def _is_device_expr(self, e):
        """Does ``e`` (an expression) yield / contain a device value?
        Host-producing subtrees (``len(...)``, ``x.shape``,
        ``jax.device_get(...)``) are pruned, not descended into."""
        if e is None:
            return False
        if isinstance(e, ast.Call):
            q = self.mod.qual(e.func)
            if q == "jax.device_get":
                return False        # the sanctioned explicit readback
            if q in _SYNC_NUMPY:
                return False        # result lives on the host
            if self.mod._is_jax_qual(q):
                return True
            if isinstance(e.func, ast.Name):
                if e.func.id in self.mod.jax_local_fns:
                    return True
                if e.func.id in (_HOST_BUILTINS | _SYNC_BUILTINS):
                    return False
            if isinstance(e.func, ast.Attribute) and \
                    e.func.attr in _SYNC_METHODS:
                return False
        elif isinstance(e, ast.Attribute) and e.attr in _STATIC_ATTRS:
            return False
        elif isinstance(e, ast.Name):
            return e.id in self.device
        return any(self._is_device_expr(c)
                   for c in ast.iter_child_nodes(e)
                   if isinstance(c, ast.expr))

    def on_call(self, call):
        target = None
        kind = None
        if isinstance(call.func, ast.Name) and \
                call.func.id in _SYNC_BUILTINS and len(call.args) == 1:
            target, kind = call.args[0], call.func.id + "()"
        elif isinstance(call.func, ast.Attribute) and \
                call.func.attr in _SYNC_METHODS and not call.args:
            target, kind = call.func.value, "." + call.func.attr + "()"
        else:
            q = self.mod.qual(call.func)
            if q in _SYNC_NUMPY and call.args:
                target, kind = call.args[0], q.replace("numpy.", "np.")
        if target is None or not self._is_device_expr(target):
            return
        if self.in_jit:
            self.mod.emit(
                call, "JX1",
                f"{kind} on a traced value inside a jit-compiled "
                f"function — concretizes at trace time / forces a "
                f"device sync")
        elif self.loop_depth > 0 and self.loop_sync:
            self.mod.emit(
                call, "JX1",
                f"per-iteration host sync: {kind} on a device value "
                f"inside a loop serializes dispatch (batch reads into "
                f"one jax.device_get)")

    def assign_target(self, target, value):
        is_dev = self._is_device_expr(value)
        for name in _bound_names(target):
            if is_dev:
                self.device.add(name)
            else:
                self.device.discard(name)

    def snapshot(self):
        return set(self.device)

    def restore(self, state):
        self.device = set(state)

    def merge(self, other):
        self.device |= other


class _KeyWalker(_FlowWalker):
    """JX2 — PRNG key reuse.

    A name is *fresh* after assignment from a key producer
    (``PRNGKey``/``split``/``fold_in``/...), *used* once any
    ``jax.random.*`` call consumes it, and a second consumption
    without a rebind is a finding."""

    def __init__(self, mod):
        super().__init__(mod)
        self.state = {}     # name -> "fresh" | "used"

    def on_call(self, call):
        q = self.mod.qual(call.func)
        if q is None or not q.startswith("jax.random."):
            return
        if q in _NON_CONSUMERS or not call.args:
            return
        name = _dotted_target(call.args[0])
        if name is None:
            return
        if self.state.get(name) == "used":
            self.mod.emit(
                call, "JX2",
                f"PRNG key '{name}' reused — already consumed by an "
                f"earlier jax.random call; split it first "
                f"(identical randomness otherwise)")
        else:
            self.state[name] = "used"

    def assign_target(self, target, value):
        fresh = False
        if isinstance(value, ast.Call):
            q = self.mod.qual(value.func)
            fresh = q in _KEY_PRODUCERS
        for node in ast.walk(target) if target is not None else ():
            if isinstance(node, ast.Name):
                if fresh:
                    self.state[node.id] = "fresh"
                else:
                    self.state.pop(node.id, None)

    def snapshot(self):
        return dict(self.state)

    def restore(self, state):
        self.state = dict(state)

    def merge(self, other):
        for k, v in other.items():
            if v == "used" or self.state.get(k) == "used":
                self.state[k] = "used"
            else:
                self.state.setdefault(k, v)


class _DonationWalker(_FlowWalker):
    """JX3 — use-after-donation.

    Tracks callables bound from ``jax.jit(..., donate_argnums=...)``
    (chased through ``.lower().compile()`` chains) plus module-level
    decorated donators; after a call, the names (or dotted paths like
    ``cache.kp``) passed in donated positions are poisoned until
    rebound."""

    def __init__(self, mod):
        super().__init__(mod)
        self.donators = dict(mod.donators)
        self.poisoned = {}        # name -> donation call line

    def on_call(self, call):
        name = None
        if isinstance(call.func, ast.Name):
            name = call.func.id
        pos = self.donators.get(name)
        if not pos:
            return
        for i in pos:
            if i < len(call.args):
                arg = _dotted_target(call.args[i])
                if arg is not None:
                    self.poisoned[arg] = call.lineno

    def on_load(self, node):
        path = _dotted_target(node)
        if path is None:
            return
        line = self.poisoned.get(path)
        if line is not None:
            self.mod.emit(
                node, "JX3",
                f"'{path}' read after being donated to a jitted call "
                f"(line {line}) — the buffer may be aliased or freed; "
                f"rebind it from the call's results")

    def assign_target(self, target, value):
        if target is None:
            return
        if isinstance(value, ast.Call):
            donate = self.mod.jit_binding(value)
            if donate:
                for node in ast.walk(target):
                    if isinstance(node, ast.Name):
                        self.donators[node.id] = donate
                return
        for node in ast.walk(target):
            if isinstance(node, (ast.Name, ast.Attribute)):
                path = _dotted_target(node)
                if path is not None:
                    self.poisoned.pop(path, None)

    def snapshot(self):
        return (dict(self.poisoned), dict(self.donators))

    def restore(self, state):
        self.poisoned, self.donators = dict(state[0]), dict(state[1])

    def merge(self, other):
        self.poisoned.update(other[0])
        self.donators.update(other[1])


# -- public API ------------------------------------------------------


def analyze_source(src, rel_path, *,
                   host_only_prefixes=HOST_ONLY_PREFIXES,
                   loop_sync_prefixes=LOOP_SYNC_PREFIXES):
    """Analyze one file's source; returns suppression-filtered
    findings (baseline NOT applied — that is repo-level)."""
    try:
        mod = _Module(src, rel_path)
    except SyntaxError:
        return []      # dev/lint.py's E999 owns syntax errors
    return mod.analyze(host_only_prefixes=host_only_prefixes,
                       loop_sync_prefixes=loop_sync_prefixes)


def analyze_file(path, repo_root, **cfg):
    with open(path, encoding="utf-8") as f:
        src = f.read()
    return analyze_source(src, os.path.relpath(path, repo_root), **cfg)


def format_baseline_entry(finding):
    return f"{finding.path}:{finding.rule}:{finding.source}"


def load_baseline(path=BASELINE_PATH):
    """Baseline entries, one fingerprint per line; '#' comments and
    blanks ignored. Returns list of (path, rule, source) tuples."""
    entries = []
    if not os.path.exists(path):
        return entries
    with open(path, encoding="utf-8") as f:
        for raw in f:
            line = raw.strip()
            if not line or line.startswith("#"):
                continue
            parts = line.split(":", 2)
            if len(parts) == 3:
                entries.append((parts[0], parts[1], parts[2]))
    return entries


def apply_baseline(findings, entries):
    """Split ``findings`` against the baseline. Returns
    ``(new_findings, stale_entries)`` — a baseline entry covers every
    finding with the same (path, rule, stripped-source) fingerprint,
    so findings survive unrelated line-number churn; entries matching
    nothing are stale and must be pruned."""
    covered = set(entries)
    new = [f for f in findings if f.fingerprint() not in covered]
    hit = {f.fingerprint() for f in findings}
    stale = [e for e in entries if e not in hit]
    return new, stale


def run(paths, repo_root, *, baseline_path=BASELINE_PATH, **cfg):
    """Analyze many files; returns (new_findings, stale_entries)."""
    findings = []
    for p in paths:
        findings.extend(analyze_file(p, repo_root, **cfg))
    entries = load_baseline(baseline_path)
    return apply_baseline(findings, entries)
