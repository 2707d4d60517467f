"""reprolint rules RL001–RL007: the repo's serving-path invariants.

Each rule protects a specific BENCH claim (see docs/lint.md for the full
mapping). The common theme: the paper's GDR-vs-TCP deltas are latency
*accounting* claims, so anything that silently moves host work, XLA
compiles, or blocking waits into (or out of) a timed stage is a
measurement bug even when the tokens come out right.

All rules are AST-only (no imports of the scanned code) and resolve
names through each module's import aliases, so ``import jax.numpy as
jnp`` / ``from jax import jit as J`` can't dodge them. Cross-module
resolution is deliberately out of scope: a callable imported from
another file is not analyzed (documented limitation — keep hot-path
helpers local to their module or suppress with a justification).
"""

from __future__ import annotations

import ast
from typing import Optional

from .core import Context, Finding, Module, rule

# stage names a RequestRecord charges; a function that both reads the
# perf_counter clock and charges one of these is a "timed-stage function"
STAGE_NAMES = {
    "queue", "preprocess", "inference", "transfer",
    "request", "response", "copy_in", "copy_out",
}

# files whose timed stages feed BENCH latency claims (RL001's scope)
HOT_PATH_FILES = (
    "serving/engine.py", "serving/disagg.py", "serving/cluster.py",
)

# expressions that force a device->host sync (or an eager device
# round-trip) when applied to device values
_NP_MATERIALIZE = {"numpy.asarray", "numpy.array"}


def _in_hot_file(mod: Module) -> bool:
    return mod.rel.endswith(HOT_PATH_FILES)


def _in_serving(mod: Module) -> bool:
    return "serving/" in mod.rel


def _walk_local(node: ast.AST):
    """Walk a function body without descending into nested function or
    class definitions (their lines belong to their own scope)."""
    stack = list(ast.iter_child_nodes(node))
    while stack:
        child = stack.pop()
        yield child
        if not isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef, ast.Lambda)):
            stack.extend(ast.iter_child_nodes(child))


def _is_perf_counter(mod: Module, node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and mod.call_name(node) == "time.perf_counter")


def _charges_stage(mod: Module, call: ast.Call) -> bool:
    """``<rec>.add("preprocess", dt)``-shaped stage charge."""
    return (isinstance(call.func, ast.Attribute)
            and call.func.attr == "add"
            and call.args
            and isinstance(call.args[0], ast.Constant)
            and call.args[0].value in STAGE_NAMES)


def _is_timed_stage_function(mod: Module, fn: ast.AST) -> bool:
    """Timed-stage function: reads the stage clock AND charges a request
    stage. (The designated blockers — the sync ``_harvest`` and the
    pipeline's harvest thread — read the clock but charge nothing, so
    they fall outside this definition by construction.)"""
    reads_clock = charges = False
    for node in _walk_local(fn):
        if isinstance(node, ast.Call):
            if mod.call_name(node) == "time.perf_counter":
                reads_clock = True
            if _charges_stage(mod, node):
                charges = True
        if reads_clock and charges:
            return True
    return False


def _contains_device_expr(mod: Module, node: ast.AST) -> bool:
    """Heuristic: the subtree eagerly touches device values (a ``jax.*``
    / ``jax.numpy.*`` call or a ``.block_until_ready()``)."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Call):
            name = mod.call_name(sub)
            if name and (name == "jax" or name.startswith(("jax.",))):
                return True
            if (isinstance(sub.func, ast.Attribute)
                    and sub.func.attr == "block_until_ready"):
                return True
    return False


def _first_arg(call: ast.Call) -> Optional[ast.AST]:
    return call.args[0] if call.args else None


# --------------------------------------------------------------------------- #
@rule(
    "RL001", "host-sync-in-hot-path",
    "no device->host sync inside a timed-stage function (only the "
    "pipeline's designated harvest thread may block)",
    interested=_in_hot_file,
)
def rl001(mod: Module, ctx: Context) -> list:
    findings = []
    for qual, fn in mod.functions():
        if not _is_timed_stage_function(mod, fn):
            continue
        for node in _walk_local(fn):
            if not isinstance(node, ast.Call):
                continue
            name = mod.call_name(node)
            hit = None
            if name in ("jax.device_get", "jax.block_until_ready"):
                hit = name
            elif (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "block_until_ready"):
                hit = ".block_until_ready()"
            elif (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item" and not node.args):
                hit = ".item()"
            elif name in _NP_MATERIALIZE:
                arg = _first_arg(node)
                # host literals / fresh numpy results never sync a device
                host_only = isinstance(arg, (
                    ast.List, ast.Tuple, ast.Dict, ast.Constant,
                    ast.ListComp, ast.GeneratorExp,
                )) or (isinstance(arg, ast.Call)
                       and (mod.call_name(arg) or "").startswith("numpy."))
                if arg is not None and not host_only:
                    hit = name
            elif name in ("float", "int"):
                arg = _first_arg(node)
                if arg is not None and _contains_device_expr(mod, arg):
                    hit = f"{name}() over a device expression"
            if hit:
                findings.append(Finding(
                    "RL001", mod.rel, node.lineno, qual,
                    f"host sync `{hit}` inside timed-stage function "
                    f"`{qual}` — stage clocks are running; only the "
                    f"designated harvest thread may block",
                ))
    return findings


# --------------------------------------------------------------------------- #
# RL002: impure jit
# --------------------------------------------------------------------------- #
_JIT_WRAPPERS = {
    "jax.jit", "jax.pmap", "jax.shard_map",
    "jax.experimental.shard_map.shard_map",
    "jax.experimental.pallas.pallas_call",
}


def _jit_wrapper_name(mod: Module, call: ast.Call) -> Optional[str]:
    name = mod.call_name(call)
    if name in _JIT_WRAPPERS:
        return name
    # functools.partial(jax.jit, ...) — the decorator idiom
    if name == "functools.partial" and call.args:
        inner = mod.resolve(call.args[0])
        if inner in _JIT_WRAPPERS:
            return inner
    return None


def _local_defs(mod: Module) -> dict:
    """name -> [function nodes] for every def in the module (methods and
    nested defs included; bare-name keyed — good enough for resolution
    inside one file)."""
    out: dict[str, list] = {}
    for qual, fn in mod.functions():
        out.setdefault(fn.name, []).append((qual, fn))
    return out


def _jit_roots(mod: Module):
    """Yield (reason, func_node_or_lambda, qualname) for every function
    this module passes into a jit/shard_map/pallas_call wrapper."""
    defs = _local_defs(mod)

    def resolve_target(node):
        """A function-valued argument -> matching local defs/lambdas."""
        if isinstance(node, ast.Lambda):
            enc = mod.enclosing_function(node.lineno)
            yield (f"{enc[0]}.<lambda>" if enc else "<lambda>"), node
        elif isinstance(node, ast.Name):
            for qual, fn in defs.get(node.id, []):
                yield qual, fn
        elif (isinstance(node, ast.Attribute)
                and isinstance(node.value, ast.Name)
                and node.value.id == "self"):
            for qual, fn in defs.get(node.attr, []):
                yield qual, fn
        elif (isinstance(node, ast.Call)
                and mod.call_name(node) == "functools.partial"
                and node.args):
            yield from resolve_target(node.args[0])

    for node in ast.walk(mod.tree):
        # decorators: @jax.jit / @functools.partial(jax.jit, ...)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                name = (mod.resolve(dec) if not isinstance(dec, ast.Call)
                        else _jit_wrapper_name(mod, dec))
                if name in _JIT_WRAPPERS:
                    for qual, fn in defs.get(node.name, []):
                        if fn is node:
                            yield name, fn, qual
        # call form: jax.jit(f), pl.pallas_call(kernel, ...), shard_map(f)
        if isinstance(node, ast.Call):
            wrapper = _jit_wrapper_name(mod, node)
            if wrapper and node.args:
                for qual, fn in resolve_target(node.args[0]):
                    yield wrapper, fn, qual


def _reachable_jitted(mod: Module, roots):
    """Transitive closure of jit roots through same-module calls (plain
    names and ``self.<method>``)."""
    defs = _local_defs(mod)
    seen: dict[int, tuple] = {}
    work = list(roots)
    while work:
        wrapper, fn, qual = work.pop()
        if id(fn) in seen:
            continue
        seen[id(fn)] = (wrapper, fn, qual)
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            callee = None
            if isinstance(node.func, ast.Name):
                callee = node.func.id
            elif (isinstance(node.func, ast.Attribute)
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "self"):
                callee = node.func.attr
            if callee:
                for q2, fn2 in defs.get(callee, []):
                    if id(fn2) not in seen:
                        work.append((wrapper, fn2, q2))
    return seen.values()


@rule(
    "RL002", "impure-jit",
    "no host clocks, host RNG, printing, or closed-over-state mutation "
    "inside a function traced by jit/shard_map/pallas_call",
)
def rl002(mod: Module, ctx: Context) -> list:
    findings = []
    for wrapper, fn, qual in _reachable_jitted(mod, _jit_roots(mod)):
        for node in _walk_local(fn):
            msg = None
            if isinstance(node, ast.Call):
                name = mod.call_name(node)
                if name and name.startswith("time."):
                    msg = f"host clock `{name}`"
                elif name and name.startswith("numpy.random"):
                    msg = f"host RNG `{name}`"
                elif name and (name == "random"
                               or name.startswith("random.")):
                    msg = f"host RNG `{name}`"
                elif name == "print":
                    msg = "`print` (host side effect, runs at trace time)"
            elif isinstance(node, (ast.Global, ast.Nonlocal)):
                msg = (f"`{'global' if isinstance(node, ast.Global) else 'nonlocal'}"
                       f" {', '.join(node.names)}` (mutates closed-over state)")
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    if (isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"):
                        msg = (f"assignment to `self.{t.attr}` (traced "
                               f"functions must not mutate Python state — "
                               f"the write happens once, at trace time)")
            if msg:
                findings.append(Finding(
                    "RL002", mod.rel, node.lineno, qual,
                    f"impure jit: {msg} inside `{qual}`, traced via "
                    f"`{wrapper.rsplit('.', 1)[-1]}`",
                ))
    return findings


# --------------------------------------------------------------------------- #
# RL003: lock discipline
# --------------------------------------------------------------------------- #
_BLOCKING_SIMPLE = {"jax.device_get", "time.sleep"}
_BLOCKING_ATTRS = {
    "block_until_ready", "sendall", "recv", "accept", "connect", "join",
}
_QUEUE_CTORS = {"queue.Queue", "queue.LifoQueue", "queue.PriorityQueue"}


def _guarded_decl(mod: Module, cls: ast.ClassDef):
    """(guarded_attrs, lock_attr) from a class-level
    ``_REPROLINT_GUARDED = ("attr", ...)`` declaration (None, None when
    the class opts out). Lock attr defaults to ``_lock``; override with
    ``_REPROLINT_LOCK = "name"``."""
    guarded, lock = None, "_lock"
    for stmt in cls.body:
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name):
            if stmt.targets[0].id == "_REPROLINT_GUARDED":
                from .core import _string_elements
                guarded = _string_elements(stmt.value)
            elif stmt.targets[0].id == "_REPROLINT_LOCK" \
                    and isinstance(stmt.value, ast.Constant):
                lock = str(stmt.value.value)
    return guarded, lock


def _queue_attrs(mod: Module, cls: ast.ClassDef) -> set:
    """self-attributes assigned from a queue.Queue(...) constructor."""
    out = set()
    for node in ast.walk(cls):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            name = mod.call_name(node.value)
            if name in _QUEUE_CTORS:
                for t in node.targets:
                    if (isinstance(t, ast.Attribute)
                            and isinstance(t.value, ast.Name)
                            and t.value.id == "self"):
                        out.add(t.attr)
    return out


def _lock_spans(mod: Module, fn: ast.AST, lock_attr: str) -> list:
    """(start, end) line spans of ``with self.<lock>:`` bodies."""
    spans = []
    for node in _walk_local(fn):
        if isinstance(node, (ast.With, ast.AsyncWith)):
            for item in node.items:
                if mod.resolve(item.context_expr) == f"self.{lock_attr}":
                    spans.append((node.lineno, node.end_lineno or node.lineno))
    return spans


def _in_spans(line: int, spans: list) -> bool:
    return any(s <= line <= e for s, e in spans)


def _direct_blocking(mod: Module, fn: ast.AST, queue_attrs: set) -> list:
    """(line, description) for blocking primitives in a function body:
    device syncs, sleeps, socket ops, joins, and bounded-queue put/get on
    a known queue attribute."""
    out = []
    for node in _walk_local(fn):
        if not isinstance(node, ast.Call):
            continue
        name = mod.call_name(node)
        if name in _BLOCKING_SIMPLE:
            out.append((node.lineno, f"`{name}`"))
        elif isinstance(node.func, ast.Attribute):
            attr = node.func.attr
            if attr in _BLOCKING_ATTRS:
                out.append((node.lineno, f"`.{attr}()`"))
            elif attr in ("put", "get"):
                recv = mod.resolve(node.func.value)
                if recv and recv.startswith("self.") \
                        and recv[len("self."):] in queue_attrs:
                    out.append((node.lineno, f"`{recv}.{attr}()`"))
    return out


@rule(
    "RL003", "lock-discipline",
    "declared lock-guarded attributes only touched under the lock, and "
    "no blocking call while the lock is held",
)
def rl003(mod: Module, ctx: Context) -> list:
    findings = []
    for cls in mod.classes():
        guarded, lock_attr = _guarded_decl(mod, cls)
        if guarded is None:
            continue
        queue_attrs = _queue_attrs(mod, cls)
        # methods whose body blocks (for the helper-under-lock check):
        # name -> description of the first blocking primitive inside
        blockers: dict[str, str] = {}
        methods = [n for n in cls.body
                   if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
        for m in methods:
            hits = _direct_blocking(mod, m, queue_attrs)
            # a helper that takes a queue as a parameter and puts/gets on
            # it blocks too — detect by bare put/get with a timeout kwarg
            for node in _walk_local(m):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr in ("put", "get")
                        and isinstance(node.func.value, ast.Name)
                        and any(kw.arg == "timeout" for kw in node.keywords)):
                    hits.append(
                        (node.lineno,
                         f"`{node.func.value.id}.{node.func.attr}(timeout=)`")
                    )
            if hits:
                blockers[m.name] = hits[0][1]
        for m in methods:
            spans = _lock_spans(mod, m, lock_attr)
            qual = f"{cls.name}.{m.name}"
            if m.name != "__init__":
                # guarded attributes touched outside the lock
                for node in _walk_local(m):
                    if (isinstance(node, ast.Attribute)
                            and isinstance(node.value, ast.Name)
                            and node.value.id == "self"
                            and node.attr in guarded
                            and not _in_spans(node.lineno, spans)):
                        findings.append(Finding(
                            "RL003", mod.rel, node.lineno, qual,
                            f"lock-guarded attribute `self.{node.attr}` "
                            f"accessed outside `with self.{lock_attr}` "
                            f"in `{qual}`",
                        ))
            # blocking calls while the lock is held
            for line, desc in _direct_blocking(mod, m, queue_attrs):
                if _in_spans(line, spans):
                    findings.append(Finding(
                        "RL003", mod.rel, line, qual,
                        f"blocking call {desc} while holding "
                        f"`self.{lock_attr}` in `{qual}` — the deadlock "
                        f"shape: a full queue parks every thread that "
                        f"needs the lock",
                    ))
            for node in _walk_local(m):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and isinstance(node.func.value, ast.Name)
                        and node.func.value.id == "self"
                        and node.func.attr in blockers
                        and _in_spans(node.lineno, spans)):
                    findings.append(Finding(
                        "RL003", mod.rel, node.lineno,
                        f"{cls.name}.{m.name}",
                        f"call to blocking helper `self.{node.func.attr}` "
                        f"(contains {blockers[node.func.attr]}) while "
                        f"holding `self.{lock_attr}` in "
                        f"`{cls.name}.{m.name}`",
                    ))
    return findings


# --------------------------------------------------------------------------- #
# RL004: IPC frame safety
# --------------------------------------------------------------------------- #
# terminal names that hold device arrays / param pytrees in this repo
_DEVICE_STATE_NAMES = {
    "params", "prefill_params", "decode_params", "caches", "blocks",
    "page_table", "_prefix_store_blocks",
}
_FRAME_FUNCS = {"send_msg", "dumps", "_call", "start_init"}
# jax introspection that returns host scalars, not arrays — safe to ship
_JAX_SCALAR_CALLS = {
    "jax.device_count", "jax.local_device_count", "jax.process_index",
    "jax.process_count",
}


def _device_leak(mod: Module, node: ast.AST, defs: dict,
                 depth: int = 1) -> Optional[str]:
    """First device-state reference reachable from a payload expression:
    a banned terminal name, a jax/jnp call, or (one level deep) a local
    function whose returns leak."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in _DEVICE_STATE_NAMES:
            return f"`{sub.id}`"
        if isinstance(sub, ast.Attribute) and sub.attr in _DEVICE_STATE_NAMES:
            return f"`.{sub.attr}`"
        if isinstance(sub, ast.Call):
            name = mod.call_name(sub)
            if name and name.startswith(("jax.", "jax.numpy.")) \
                    and name not in _JAX_SCALAR_CALLS:
                return f"`{name}(...)`"
            if depth > 0 and isinstance(sub.func, ast.Name):
                for _q, fn in defs.get(sub.func.id, []):
                    for ret in ast.walk(fn):
                        if isinstance(ret, ast.Return) and ret.value:
                            leak = _device_leak(mod, ret.value, defs,
                                                depth - 1)
                            if leak:
                                return (f"{leak} via local "
                                        f"`{sub.func.id}()`")
    return None


@rule(
    "RL004", "ipc-frame-safety",
    "no jax.Array / param pytree reachable from an object pickled into "
    "an IPC frame — params never cross the wire",
)
def rl004(mod: Module, ctx: Context) -> list:
    findings = []
    defs = _local_defs(mod)
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        name = mod.call_name(node) or ""
        terminal = name.rsplit(".", 1)[-1]
        if terminal not in _FRAME_FUNCS:
            continue
        if terminal == "dumps" and not name.startswith("pickle."):
            continue
        enc = mod.enclosing_function(node.lineno)
        qual = enc[0] if enc else "<module>"
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            # the socket / op-string positions can't carry pytrees;
            # scanning them too is harmless (their names aren't banned)
            leak = _device_leak(mod, arg, defs)
            if leak:
                findings.append(Finding(
                    "RL004", mod.rel, node.lineno, qual,
                    f"device state {leak} reachable from the payload of "
                    f"IPC frame call `{terminal}` in `{qual}` — params "
                    f"and KV never cross the wire (workers rebuild from "
                    f"the seed)",
                ))
                break
    return findings


# --------------------------------------------------------------------------- #
# RL005: warmup coverage
# --------------------------------------------------------------------------- #
def _jit_register_candidates(mod: Module, call: ast.Call) -> tuple:
    """(candidates, line, qual) naming a ``jax.jit(...)`` creation site:
    the assignment target's terminal name (attribute / name / subscript
    base), falling back to the enclosing function's name."""
    node: ast.AST = call
    names: set[str] = set()
    while node is not None:
        parent = getattr(node, "_reprolint_parent", None)
        if isinstance(parent, ast.Assign):
            for t in parent.targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
                elif isinstance(t, ast.Attribute):
                    names.add(t.attr)
                elif isinstance(t, ast.Subscript):
                    base = t.value
                    if isinstance(base, ast.Attribute):
                        names.add(base.attr)
                    elif isinstance(base, ast.Name):
                        names.add(base.id)
            break
        if isinstance(parent, (ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.Module)) or parent is None:
            break
        node = parent
    enc = mod.enclosing_function(call.lineno)
    qual = enc[0] if enc else "<module>"
    if not names:
        names.add(qual.rsplit(".", 1)[-1])
    # allow Class.attr-qualified table entries too
    for n in list(names):
        if "." in qual:
            names.add(f"{qual.split('.')[0]}.{n}")
    return names, call.lineno, qual


@rule(
    "RL005", "warmup-coverage",
    "every jax.jit created in serving/ is registered in the "
    "WARM_PRETRACE_TABLE (pre-traced at construction) or suppressed "
    "with a reason",
    interested=_in_serving,
)
def rl005(mod: Module, ctx: Context) -> list:
    findings = []
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.Call) and mod.call_name(node) == "jax.jit":
            names, line, qual = _jit_register_candidates(mod, node)
            if not ctx.in_warm_table(names):
                pretty = sorted(n for n in names if "." not in n) or \
                    sorted(names)
                findings.append(Finding(
                    "RL005", mod.rel, line, qual,
                    f"jit `{pretty[0]}` is not in WARM_PRETRACE_TABLE — "
                    f"an unwarmed jit compiles inside a timed stage on "
                    f"first use (register it in the table once warm() "
                    f"pre-traces it, or suppress with the reason it "
                    f"cannot be pre-traced)",
                ))
    return findings


# --------------------------------------------------------------------------- #
# RL006: swallowed-failure hygiene
# --------------------------------------------------------------------------- #
def _routes_failures(fn: ast.AST) -> bool:
    """True when the function body contains a try/except whose handler
    does real capture work (not just pass/continue) — the minimum for a
    daemon thread whose exceptions would otherwise vanish."""
    for node in ast.walk(fn):
        if isinstance(node, ast.Try):
            for h in node.handlers:
                if any(not isinstance(stmt, (ast.Pass, ast.Continue))
                       for stmt in h.body):
                    return True
    return False


@rule(
    "RL006", "swallowed-failure-hygiene",
    "no bare `except:`; every daemon-thread target routes its "
    "exceptions to a failure-capture path",
)
def rl006(mod: Module, ctx: Context) -> list:
    findings = []
    defs = _local_defs(mod)
    for node in ast.walk(mod.tree):
        if isinstance(node, ast.ExceptHandler) and node.type is None:
            enc = mod.enclosing_function(node.lineno)
            qual = enc[0] if enc else "<module>"
            findings.append(Finding(
                "RL006", mod.rel, node.lineno, qual,
                f"bare `except:` in `{qual}` swallows every failure "
                f"(KeyboardInterrupt and SystemExit included) — catch "
                f"something and route it",
            ))
        if isinstance(node, ast.Call) \
                and mod.call_name(node) == "threading.Thread":
            kwargs = {kw.arg: kw.value for kw in node.keywords}
            daemon = kwargs.get("daemon")
            if not (isinstance(daemon, ast.Constant) and daemon.value):
                continue
            target = kwargs.get("target")
            target_defs = []
            if isinstance(target, ast.Name):
                target_defs = defs.get(target.id, [])
                tname = target.id
            elif (isinstance(target, ast.Attribute)
                    and isinstance(target.value, ast.Name)
                    and target.value.id == "self"):
                target_defs = defs.get(target.attr, [])
                tname = target.attr
            else:
                continue  # unresolvable target: out of scope
            enc = mod.enclosing_function(node.lineno)
            qual = enc[0] if enc else "<module>"
            if target_defs and not any(_routes_failures(fn)
                                       for _q, fn in target_defs):
                findings.append(Finding(
                    "RL006", mod.rel, node.lineno, qual,
                    f"daemon thread target `{tname}` has no "
                    f"failure-capture: an exception kills the thread "
                    f"silently and the pipeline wedges (wrap the body "
                    f"and surface the traceback like "
                    f"EnginePipeline._run_guarded)",
                ))
    return findings


# --------------------------------------------------------------------------- #
# RL007: trace coverage
# --------------------------------------------------------------------------- #
def _emits_trace(mod: Module, fn: ast.AST) -> bool:
    """True when the function body reaches a span emitter: a ``.emit()``
    call (``trace.tracer().emit(...)``), a ``trace.span(...)`` context,
    or a call to a ``_trace*`` helper (the engine's span emitters)."""
    for node in _walk_local(fn):
        if not isinstance(node, ast.Call):
            continue
        if isinstance(node.func, ast.Attribute):
            attr = node.func.attr
            if attr in ("emit", "span") or attr.startswith("_trace"):
                return True
        elif isinstance(node.func, ast.Name) \
                and node.func.id.startswith("_trace"):
            return True
    return False


@rule(
    "RL007", "trace-coverage",
    "every timed-stage function in a hot-path file also emits a span "
    "(via .emit(), a trace.span() context or a _trace* helper) so charged "
    "stages stay reconcilable against the trace",
    interested=_in_hot_file,
)
def rl007(mod: Module, ctx: Context) -> list:
    findings = []
    for qual, fn in mod.functions():
        if not _is_timed_stage_function(mod, fn):
            continue
        if _emits_trace(mod, fn):
            continue
        findings.append(Finding(
            "RL007", mod.rel, fn.lineno, qual,
            f"timed-stage function `{qual}` charges a stage but emits no "
            f"span — Trace.reconcile() cannot cross-check its charge "
            f"(call trace.tracer().emit(...), open trace.span(...) or "
            f"call a _trace* helper, or "
            f"suppress with the reason the stage is trace-exempt)",
        ))
    return findings
