"""narwhal-lint rules — each grounded in a failure this repo actually paid for.

| rule                      | incident it guards against                        |
|---------------------------|---------------------------------------------------|
| no-blocking-in-async      | event-loop stalls starving every co-hosted actor  |
| no-raw-queue              | unmetered actor edges (no depth gauge, no bound)  |
| tracked-task-spawn        | the PR-1 shutdown wedge: dropped task handles     |
| jit-purity                | host side effects baked into a traced TPU kernel  |
| no-shared-decode-mutation | the ADVICE r5 medium: decode-cache corruption     |
| no-silent-except          | swallowed failures in the consensus-critical dirs |
| no-per-item-rpc-in-loop   | RTT x items serialization on the commit data plane|
| no-unbounded-channel      | default-capacity edges defeating admission control|
| no-wall-clock-in-actors   | wall time leaking past the simnet virtual clock   |
| no-untracked-jit          | duplicate multi-minute kernel compiles (rc=124)   |
| metric-naming             | scrape-surface drift: unparseable/unitless names  |

Rules are pure `ast` visitors over one `Module` at a time; registration is
import-time via the `@register` decorator so `RULES` is the single catalog
the CLI, the baseline, and the tests all share. Adding a rule = subclass
`Rule`, decorate, ship a tripping + clean fixture (see
tests/lint_fixtures/) — the catalog test enforces the fixture pairing.
"""

from __future__ import annotations

import ast
import re
from pathlib import PurePath
from typing import Iterable, Iterator

from .engine import Finding, Module

RULES: dict[str, "Rule"] = {}


def register(cls: type["Rule"]) -> type["Rule"]:
    rule = cls()
    assert rule.name not in RULES, f"duplicate rule {rule.name}"
    RULES[rule.name] = rule
    return cls


class Rule:
    name: str = ""
    summary: str = ""

    def check(self, mod: Module) -> Iterator[Finding]:
        raise NotImplementedError

    def finding(self, mod: Module, node: ast.AST, message: str) -> Finding:
        return mod.finding(self.name, node, message)


# ---------------------------------------------------------------------------
# Shared AST helpers
# ---------------------------------------------------------------------------


def dotted(node: ast.AST) -> str | None:
    """Render a Name/Attribute chain as 'a.b.c'; None for anything else."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def import_aliases(tree: ast.Module) -> dict[str, str]:
    """Local name -> canonical dotted origin, for both import forms:
    `import numpy as np` -> {'np': 'numpy'};
    `from time import sleep as zzz` -> {'zzz': 'time.sleep'}."""
    aliases: dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                aliases[a.asname or a.name.split(".")[0]] = (
                    a.name if a.asname else a.name.split(".")[0]
                )
        elif isinstance(node, ast.ImportFrom) and node.module:
            for a in node.names:
                aliases[a.asname or a.name] = f"{node.module}.{a.name}"
    return aliases


def resolve(node: ast.AST, aliases: dict[str, str]) -> str | None:
    """dotted() with the leading segment mapped through the import table,
    so `sp.run` resolves to `subprocess.run` under `import subprocess as sp`."""
    d = dotted(node)
    if d is None:
        return None
    head, _, rest = d.partition(".")
    origin = aliases.get(head)
    if origin is None:
        return d
    return f"{origin}.{rest}" if rest else origin


def own_nodes(func: ast.AST) -> Iterator[ast.AST]:
    """Walk a function's body WITHOUT descending into nested function
    definitions (those run on their own schedule, often in executors)."""
    stack = list(getattr(func, "body", []))
    while stack:
        node = stack.pop()
        yield node
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            stack.append(child)


def in_dirs(mod: Module, names: frozenset[str]) -> bool:
    return bool(names.intersection(PurePath(mod.rel).parts))


# ---------------------------------------------------------------------------
# no-blocking-in-async
# ---------------------------------------------------------------------------


@register
class NoBlockingInAsync(Rule):
    name = "no-blocking-in-async"
    summary = (
        "async def bodies must not call blocking primitives (time.sleep, "
        "sync file/socket I/O, subprocess, bare future .result()); one "
        "stalled coroutine starves every actor sharing the loop"
    )

    BLOCKING = {
        "time.sleep": "use `await asyncio.sleep(...)`",
        "os.system": "use `await asyncio.create_subprocess_shell(...)`",
        "os.popen": "use `await asyncio.create_subprocess_shell(...)`",
        "subprocess.run": "use asyncio.create_subprocess_exec",
        "subprocess.call": "use asyncio.create_subprocess_exec",
        "subprocess.check_call": "use asyncio.create_subprocess_exec",
        "subprocess.check_output": "use asyncio.create_subprocess_exec",
        "subprocess.Popen": "use asyncio.create_subprocess_exec",
        "socket.socket": "use asyncio.open_connection / loop.sock_* APIs",
        "socket.create_connection": "use asyncio.open_connection",
        "open": "read/write off the loop (asyncio.to_thread) or pre-open",
        "input": "never prompt inside an event loop",
    }
    _SPAWNERS = {"ensure_future", "create_task"}

    def check(self, mod: Module) -> Iterator[Finding]:
        aliases = import_aliases(mod.tree)
        for func in ast.walk(mod.tree):
            if not isinstance(func, ast.AsyncFunctionDef):
                continue
            # Names bound from asyncio.ensure_future/create_task in THIS
            # function: .result() on those is an asyncio.Task read (raises
            # if pending, never blocks) — the done-task select-loop idiom.
            safe_tasks: set[str] = set()
            for node in own_nodes(func):
                if (
                    isinstance(node, ast.Assign)
                    and isinstance(node.value, ast.Call)
                    and isinstance(node.value.func, ast.Attribute)
                    and node.value.func.attr in self._SPAWNERS
                ):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            safe_tasks.add(t.id)
            for node in own_nodes(func):
                if not isinstance(node, ast.Call):
                    continue
                target = resolve(node.func, aliases)
                if target in self.BLOCKING:
                    yield self.finding(
                        mod,
                        node,
                        f"`{target}(...)` blocks the event loop inside "
                        f"`async def {func.name}`; {self.BLOCKING[target]}",
                    )
                    continue
                if (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr == "result"
                    and not node.args
                    and not node.keywords
                ):
                    if (
                        isinstance(node.func.value, ast.Name)
                        and node.func.value.id in safe_tasks
                    ):
                        continue  # provably an asyncio task handle
                    yield self.finding(
                        mod,
                        node,
                        "`.result()` on a future of unknown origin inside "
                        f"`async def {func.name}`: a concurrent.futures "
                        "future blocks the loop. Await it instead; if this "
                        "is a known-done asyncio task, suppress with "
                        "`# lint: allow(no-blocking-in-async)`",
                    )


# ---------------------------------------------------------------------------
# no-raw-queue
# ---------------------------------------------------------------------------


@register
class NoRawQueue(Rule):
    name = "no-raw-queue"
    summary = (
        "inter-actor edges must be metered bounded Channels (channels.py), "
        "never bare asyncio queues — the metered_channel.rs discipline: "
        "every edge has a capacity and a depth gauge"
    )

    _QUEUES = {"asyncio.Queue", "asyncio.LifoQueue", "asyncio.PriorityQueue"}

    def check(self, mod: Module) -> Iterator[Finding]:
        if mod.path.name == "channels.py":  # the one sanctioned wrapper
            return
        aliases = import_aliases(mod.tree)
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                target = resolve(node.func, aliases)
                if target in self._QUEUES:
                    yield self.finding(
                        mod,
                        node,
                        f"raw `{target}` constructed outside channels.py — "
                        "actor edges must be metered bounded Channels "
                        "(channels.Channel / metered_channel) so depth is "
                        "gauged and backpressure is bounded",
                    )


# ---------------------------------------------------------------------------
# tracked-task-spawn
# ---------------------------------------------------------------------------


@register
class TrackedTaskSpawn(Rule):
    name = "tracked-task-spawn"
    summary = (
        "a spawned task whose handle is dropped can neither be cancelled "
        "nor drained at shutdown (the PR-1 shutdown-wedge class); keep the "
        "handle in an owner that cancels it"
    )

    _SPAWNERS = {"create_task", "ensure_future"}

    def check(self, mod: Module) -> Iterator[Finding]:
        for node in ast.walk(mod.tree):
            if (
                isinstance(node, ast.Expr)
                and isinstance(node.value, ast.Call)
                and (
                    (
                        isinstance(node.value.func, ast.Attribute)
                        and node.value.func.attr in self._SPAWNERS
                    )
                    or (
                        isinstance(node.value.func, ast.Name)
                        and node.value.func.id in self._SPAWNERS
                    )
                )
            ):
                yield self.finding(
                    mod,
                    node,
                    f"`{dotted(node.value.func) or node.value.func.attr}"
                    "(...)` drops the task handle — register it with a "
                    "drainable owner (BoundedExecutor, CancelOnDrop, or an "
                    "owner task set cancelled on shutdown)",
                )


# ---------------------------------------------------------------------------
# jit-purity
# ---------------------------------------------------------------------------


@register
class JitPurity(Rule):
    name = "jit-purity"
    summary = (
        "functions reachable from a @jax.jit root in tpu/ must be pure — "
        "across module boundaries (tools/analysis/purity call graph): "
        "no print/time/random/global mutation — side effects run once at "
        "trace time then silently vanish from the compiled kernel"
    )

    _IMPURE_MODULES = {"time", "random"}
    _IMPURE_CALLS = {"print", "input"}

    def check(self, mod: Module) -> Iterator[Finding]:
        if "tpu" not in PurePath(mod.rel).parts:
            return
        yield from self._check_same_module(mod)
        yield from self._check_cross_module(mod)

    def _check_same_module(self, mod: Module) -> Iterator[Finding]:
        aliases = import_aliases(mod.tree)
        funcs: dict[str, ast.AST] = {}
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                funcs.setdefault(node.name, node)
        module_globals = {
            t.id
            for stmt in mod.tree.body
            if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign))
            for t in (stmt.targets if isinstance(stmt, ast.Assign) else [stmt.target])
            if isinstance(t, ast.Name)
        }

        roots = self._jit_roots(mod.tree, aliases, funcs)
        # Same-module call-graph BFS from the jitted roots; `via` remembers
        # which root makes each function traced, for the diagnostic.
        via: dict[str, str] = {r: r for r in roots}
        queue = list(roots)
        while queue:
            fname = queue.pop()
            for node in ast.walk(funcs[fname]):
                if isinstance(node, ast.Call):
                    callee = None
                    if isinstance(node.func, ast.Name):
                        callee = node.func.id
                    elif isinstance(node.func, ast.Attribute):
                        callee = node.func.attr  # self.helper(...) style
                    if callee in funcs and callee not in via:
                        via[callee] = via[fname]
                        queue.append(callee)

        for fname, root in via.items():
            yield from self._check_func(mod, funcs[fname], root, aliases, module_globals)

    def _check_cross_module(self, mod: Module) -> Iterator[Finding]:
        """The retired same-module caveat: BFS now continues into sibling
        modules (tools/analysis/purity). Impurities whose site lies in a
        DIFFERENT module than the jit root's declaration are reported
        while scanning the declaring module, anchored at their real site
        (an inline `# lint: allow(jit-purity)` at that site suppresses)."""
        try:
            from tools.analysis.purity import module_purity
        except ImportError:  # running outside the repo checkout
            return
        rel_dir = PurePath(mod.rel).parent
        for imp in module_purity(mod.path, mod.path.parent.parent):
            if not imp.cross_module:
                continue  # same-module findings come from _check_same_module
            if "jit-purity" in imp.allowed_rules or "*" in imp.allowed_rules:
                continue
            rel = (rel_dir / PurePath(imp.path).name).as_posix()
            yield Finding(
                self.name, rel, imp.line, imp.col, imp.message, imp.snippet
            )

    def _jit_roots(
        self, tree: ast.Module, aliases: dict[str, str], funcs: dict[str, ast.AST]
    ) -> set[str]:
        # kernel_registry.tracked_jit is the sanctioned jit wrapper in tpu/
        # (no-untracked-jit); its decoratees are jit roots exactly like raw
        # @jax.jit ones, and registry.sharded(fn, ...) wraps are the
        # sharded-kernel analog of `name = jax.jit(fn)`.
        jit_names = {
            "jax.jit",
            "jit",
            "tracked_jit",
            "kernel_registry.tracked_jit",
            "narwhal_tpu.tpu.kernel_registry.tracked_jit",
            "kernel_registry.sharded",
            "narwhal_tpu.tpu.kernel_registry.sharded",
        }
        roots: set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for deco in node.decorator_list:
                    d = resolve(deco, aliases)
                    if d in jit_names:
                        roots.add(node.name)
                    elif isinstance(deco, ast.Call):
                        f = resolve(deco.func, aliases)
                        if f in jit_names:
                            roots.add(node.name)
                        elif f in ("partial", "functools.partial") and deco.args:
                            if resolve(deco.args[0], aliases) in jit_names:
                                roots.add(node.name)
            elif isinstance(node, ast.Call):
                # name = jax.jit(fn) — wrapping a module-level function
                if resolve(node.func, aliases) in jit_names and node.args:
                    arg = node.args[0]
                    if isinstance(arg, ast.Name) and arg.id in funcs:
                        roots.add(arg.id)
        return roots

    def _check_func(
        self,
        mod: Module,
        func: ast.AST,
        root: str,
        aliases: dict[str, str],
        module_globals: set[str],
    ) -> Iterator[Finding]:
        local_names = {a.arg for a in getattr(func, "args", ast.arguments(args=[])).args}
        for node in ast.walk(func):
            if isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) else [node.target]
                for t in targets:
                    if isinstance(t, ast.Name):
                        local_names.add(t.id)
        for node in ast.walk(func):
            if isinstance(node, ast.Global):
                yield self.finding(
                    mod,
                    node,
                    f"`global {', '.join(node.names)}` inside `{func.name}` "
                    f"(reachable from jitted `{root}`): global mutation is "
                    "invisible to the traced kernel after compilation",
                )
            elif isinstance(node, ast.Call):
                target = resolve(node.func, aliases)
                if target is None:
                    continue
                head = target.split(".")[0]
                if target in self._IMPURE_CALLS or (
                    head in self._IMPURE_MODULES and head not in local_names
                ):
                    yield self.finding(
                        mod,
                        node,
                        f"impure call `{target}(...)` in `{func.name}` "
                        f"(reachable from jitted `{root}`): runs once at "
                        "trace time, then is baked into / elided from the "
                        "compiled kernel",
                    )
                elif target.startswith(("numpy.random", "np.random")):
                    yield self.finding(
                        mod,
                        node,
                        f"`{target}(...)` in `{func.name}` (reachable from "
                        f"jitted `{root}`): host RNG is trace-time constant "
                        "under jit; thread a jax.random key instead",
                    )
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) else [node.target]
                for t in targets:
                    base = t
                    hops = 0
                    while isinstance(base, (ast.Attribute, ast.Subscript)):
                        base = base.value
                        hops += 1
                    if (
                        hops
                        and isinstance(base, ast.Name)
                        and base.id in module_globals
                        and base.id not in local_names
                    ):
                        yield self.finding(
                            mod,
                            node,
                            f"mutation of module-level `{base.id}` in "
                            f"`{func.name}` (reachable from jitted "
                            f"`{root}`): happens at trace time only, not "
                            "per kernel invocation",
                        )


# ---------------------------------------------------------------------------
# no-shared-decode-mutation
# ---------------------------------------------------------------------------


@register
class NoSharedDecodeMutation(Rule):
    name = "no-shared-decode-mutation"
    summary = (
        "decoded messages are shared process-wide by the decode cache "
        "(messages._DECODE_CACHE): writing a field of one corrupts every "
        "hosted node's view (the ADVICE r5 medium)"
    )

    # Core wire types whose decoded instances flow through the caches.
    _CORE_TYPES = {"Header", "Certificate", "Vote", "Batch"}
    # The encode memo is the one sanctioned write (messages.encode_message).
    _EXEMPT_ATTRS = {"_encoded"}
    _MUTATORS = {
        "append", "extend", "insert", "remove", "add", "discard",
        "update", "setdefault", "pop", "popitem", "clear",
    }

    def check(self, mod: Module) -> Iterator[Finding]:
        msg_classes = self._message_classes(mod)
        scopes: list[ast.AST] = [mod.tree] + [
            n
            for n in ast.walk(mod.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        for scope in scopes:
            tracked = self._tracked_names(scope, msg_classes)
            for node in self._scope_nodes(scope):
                yield from self._check_node(mod, node, tracked, msg_classes)

    def _scope_nodes(self, scope: ast.AST) -> Iterator[ast.AST]:
        if isinstance(scope, ast.Module):
            # Module scope: top-level statements only; functions are their
            # own scopes so tracked-name sets don't leak across.
            for stmt in scope.body:
                if not isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield from ast.walk(stmt)
        else:
            yield from own_nodes(scope)

    def _message_classes(self, mod: Module) -> set[str]:
        names: set[str] = set()
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ImportFrom) and node.module:
                if node.module.endswith("messages"):
                    for a in node.names:
                        local = a.asname or a.name
                        if local[:1].isupper():
                            names.add(local)
                elif node.module.endswith("types"):
                    for a in node.names:
                        local = a.asname or a.name
                        if local in self._CORE_TYPES:
                            names.add(local)
            elif isinstance(node, ast.ClassDef):
                for deco in node.decorator_list:
                    if (
                        isinstance(deco, ast.Call)
                        and isinstance(deco.func, ast.Name)
                        and deco.func.id == "message"
                    ):
                        names.add(node.name)
        if mod.path.name in ("types.py", "messages.py"):
            names.update(self._CORE_TYPES)
        return names

    def _is_decode_call(self, node: ast.AST, msg_classes: set[str]) -> bool:
        if not isinstance(node, ast.Call):
            return False
        f = node.func
        if isinstance(f, ast.Name) and f.id == "decode_message":
            return True
        if isinstance(f, ast.Attribute):
            if f.attr == "decode_message":
                return True
            if f.attr in ("decode", "from_bytes") and isinstance(f.value, ast.Name):
                return f.value.id in msg_classes
        return False

    def _tracked_names(self, scope: ast.AST, msg_classes: set[str]) -> set[str]:
        tracked: set[str] = set()
        args = getattr(scope, "args", None)
        if args is not None:
            for a in list(args.args) + list(args.kwonlyargs):
                ann = a.annotation
                ann_name = None
                if isinstance(ann, ast.Name):
                    ann_name = ann.id
                elif isinstance(ann, ast.Constant) and isinstance(ann.value, str):
                    ann_name = ann.value.strip("'\"")
                if ann_name in msg_classes:
                    tracked.add(a.arg)
        for node in self._scope_nodes(scope):
            if isinstance(node, ast.Assign) and self._is_decode_call(node.value, msg_classes):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        tracked.add(t.id)
            elif isinstance(node, ast.AnnAssign) and isinstance(node.target, ast.Name):
                ann = node.target
                if isinstance(node.annotation, ast.Name) and node.annotation.id in msg_classes:
                    tracked.add(ann.id)
                elif node.value is not None and self._is_decode_call(node.value, msg_classes):
                    tracked.add(ann.id)
        return tracked

    def _root_is_tracked(
        self, node: ast.AST, tracked: set[str], msg_classes: set[str]
    ) -> bool:
        """True if an Attribute/Subscript chain bottoms out at a tracked
        name or directly at a decode call result."""
        saw_attr = isinstance(node, ast.Attribute)
        base = node
        while isinstance(base, (ast.Attribute, ast.Subscript)):
            base = base.value
            if isinstance(base, ast.Attribute):
                saw_attr = True
        if not saw_attr:
            return False
        if isinstance(base, ast.Name):
            return base.id in tracked
        return self._is_decode_call(base, msg_classes)

    def _check_node(
        self, mod: Module, node: ast.AST, tracked: set[str], msg_classes: set[str]
    ) -> Iterator[Finding]:
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.Delete)):
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, ast.AugAssign):
                targets = [node.target]
            else:
                targets = node.targets
            for t in targets:
                if (
                    isinstance(t, ast.Attribute)
                    and t.attr in self._EXEMPT_ATTRS
                ):
                    continue
                if isinstance(t, (ast.Attribute, ast.Subscript)) and self._root_is_tracked(
                    t, tracked, msg_classes
                ):
                    yield self.finding(
                        mod,
                        node,
                        "write to a field of a decoded message: decoded "
                        "objects are shared by the process-wide decode "
                        "cache across every hosted node — copy before "
                        "mutating",
                    )
        elif (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in self._MUTATORS
            and isinstance(node.func.value, (ast.Attribute, ast.Subscript))
            and self._root_is_tracked(node.func.value, tracked, msg_classes)
        ):
            yield self.finding(
                mod,
                node,
                f"`.{node.func.attr}(...)` mutates a container inside a "
                "decoded message shared by the decode cache — copy before "
                "mutating",
            )


# ---------------------------------------------------------------------------
# no-sync-store-write-in-async
# ---------------------------------------------------------------------------


@register
class NoSyncStoreWriteInAsync(Rule):
    name = "no-sync-store-write-in-async"
    summary = (
        "in primary/ and consensus/, async def bodies must use the "
        "group-commit store API (put_async/write_async/write_batch_async): "
        "a sync put/write runs its own WAL append + flush() on the event "
        "loop, paying per-message I/O the batching layer exists to remove"
    )

    _SCOPED_DIRS = frozenset({"primary", "consensus"})
    _WRITE_METHODS = {
        "put",
        "put_all",
        "write",
        "write_all",
        "write_batch",
        "write_consensus_state",
    }
    # Receiver-name heuristics for store-shaped objects: the typed stores
    # (x.header_store, certificate_store, ...), the engine, and the raw
    # column-family handles. Plain `writer.write(...)` (StreamWriter) and
    # non-store receivers never match.
    _STORE_SEGMENTS = frozenset(
        {"engine", "_engine", "_cf", "_main", "_by_round", "_last", "_seq"}
    )

    def check(self, mod: Module) -> Iterator[Finding]:
        if not in_dirs(mod, self._SCOPED_DIRS):
            return
        for func in ast.walk(mod.tree):
            if not isinstance(func, ast.AsyncFunctionDef):
                continue
            for node in own_nodes(func):
                if not (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in self._WRITE_METHODS
                ):
                    continue
                recv = dotted(node.func.value)
                if recv is None:
                    continue
                segments = recv.split(".")
                if not any(
                    "store" in seg.lower() or seg in self._STORE_SEGMENTS
                    for seg in segments
                ):
                    continue
                yield self.finding(
                    mod,
                    node,
                    f"sync store write `{recv}.{node.func.attr}(...)` "
                    f"inside `async def {func.name}`: each call is its own "
                    "WAL append + flush() on the event loop — use the "
                    f"async variant (`{node.func.attr}_async`/"
                    "`write_batch_async`) so the write rides a fused "
                    "group commit",
                )


# ---------------------------------------------------------------------------
# no-per-item-rpc-in-loop
# ---------------------------------------------------------------------------


@register
class NoPerItemRpcInLoop(Rule):
    name = "no-per-item-rpc-in-loop"
    summary = (
        "in executor/ and primary/, an awaited network RPC inside a for-loop "
        "pays one round trip per item (RTT x batches on the commit path); "
        "coalesce the digests into one batched request (RequestBatchesMsg, "
        "CertificatesBatchRequest) or fan out with asyncio.gather — bounded "
        "retry loops over ONE coalesced request carry a justified "
        "`# lint: allow(no-per-item-rpc-in-loop)`"
    )

    _SCOPED_DIRS = frozenset({"executor", "primary"})
    _RPC_METHODS = {"request", "unreliable_send"}
    # Receiver-name heuristic for RPC-client-shaped objects; plain
    # `queue.request(...)` on unrelated receivers never matches.
    _NET_SEGMENTS = frozenset(
        {"network", "_network", "net", "_net", "client", "_client", "peer"}
    )

    def check(self, mod: Module) -> Iterator[Finding]:
        if not in_dirs(mod, self._SCOPED_DIRS):
            return
        seen: set[tuple[int, int]] = set()
        for loop_node in ast.walk(mod.tree):
            if not isinstance(loop_node, (ast.For, ast.AsyncFor)):
                continue
            for node in self._loop_nodes(loop_node):
                if not (
                    isinstance(node, ast.Await)
                    and isinstance(node.value, ast.Call)
                    and isinstance(node.value.func, ast.Attribute)
                    and node.value.func.attr in self._RPC_METHODS
                ):
                    continue
                recv = dotted(node.value.func.value)
                if recv is None or not self._is_network_receiver(recv):
                    continue
                key = (node.lineno, node.col_offset)
                if key in seen:  # nested loops: report once
                    continue
                seen.add(key)
                yield self.finding(
                    mod,
                    node,
                    f"`await {recv}.{node.value.func.attr}(...)` inside a "
                    "for-loop serializes one RPC round trip per item — "
                    "coalesce the loop's items into one batched request, or "
                    "justify a bounded retry loop with "
                    "`# lint: allow(no-per-item-rpc-in-loop)`",
                )

    def _is_network_receiver(self, recv: str) -> bool:
        return any(
            seg in self._NET_SEGMENTS or "network" in seg.lower()
            for seg in recv.split(".")
        )

    def _loop_nodes(self, loop_node: ast.AST) -> Iterator[ast.AST]:
        """Walk a loop's body (and else) without descending into nested
        function definitions — a helper defined inside the loop runs on its
        own schedule (often gathered), not once per iteration."""
        stack = list(loop_node.body) + list(loop_node.orelse)
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            yield node
            stack.extend(ast.iter_child_nodes(node))


# ---------------------------------------------------------------------------
# no-unbounded-channel
# ---------------------------------------------------------------------------


@register
class NoUnboundedChannel(Rule):
    name = "no-unbounded-channel"
    summary = (
        "in worker/, primary/ and executor/ hot paths, a Channel "
        "constructed without an explicit capacity silently takes the "
        "1000-item default — an edge nobody sized, invisible to the "
        "occupancy watermarks the pacing controller and admission gate "
        "read; pass a deliberate capacity (or use metered_channel)"
    )

    _SCOPED_DIRS = frozenset({"worker", "primary", "executor"})

    def check(self, mod: Module) -> Iterator[Finding]:
        if not in_dirs(mod, self._SCOPED_DIRS):
            return
        aliases = import_aliases(mod.tree)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            target = resolve(node.func, aliases)
            if target is None or not (
                target == "Channel" or target.endswith(".Channel")
            ):
                continue
            # The first positional argument is the capacity; a capacity=
            # keyword also counts. Anything else (bare Channel(), or only
            # gauge=/other keywords) ships the unexamined default.
            if node.args:
                continue
            if any(kw.arg == "capacity" for kw in node.keywords):
                continue
            yield self.finding(
                mod,
                node,
                f"`{target}(...)` without an explicit capacity takes the "
                "default bound on a hot-path actor edge — size it "
                "deliberately so channel occupancy means something to the "
                "pacing/backpressure watermarks",
            )


# ---------------------------------------------------------------------------
# no-silent-except
# ---------------------------------------------------------------------------


@register
class NoSilentExcept(Rule):
    name = "no-silent-except"
    summary = (
        "in primary/, worker/, consensus/, network/: an except that "
        "swallows without logging hides the exact failures (wedges, "
        "deadlocks) rounds 4-5 spent days reconstructing from timeouts"
    )

    _SCOPED_DIRS = frozenset({"primary", "worker", "consensus", "network"})
    _BROAD = {"Exception", "BaseException"}
    _LOG_METHODS = {
        "debug", "info", "warning", "warn", "error", "exception", "critical", "log",
    }

    def check(self, mod: Module) -> Iterator[Finding]:
        if not in_dirs(mod, self._SCOPED_DIRS):
            return
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            body = [
                s
                for s in node.body
                if not (
                    isinstance(s, ast.Expr)
                    and isinstance(s.value, ast.Constant)
                    and isinstance(s.value.value, str)
                )
            ]
            handled = self._handles(body)
            caught = self._caught_names(node)
            if all(
                isinstance(s, (ast.Pass, ast.Continue))
                or (
                    isinstance(s, ast.Expr)
                    and isinstance(s.value, ast.Constant)
                    and s.value.value is Ellipsis
                )
                for s in body
            ):
                yield self.finding(
                    mod,
                    node,
                    f"except {caught or '<all>'} silently swallows the "
                    "error — log it (logger.debug at minimum), re-raise, "
                    "or suppress with a one-line justification",
                )
            elif (
                not handled
                and (node.type is None or self._BROAD.intersection(self._caught_set(node)))
            ):
                yield self.finding(
                    mod,
                    node,
                    f"broad `except {caught or ''}` without logging or "
                    "re-raise: narrow the exception types, or log what was "
                    "swallowed",
                )

    def _caught_set(self, node: ast.ExceptHandler) -> set[str]:
        t = node.type
        out: set[str] = set()
        if isinstance(t, ast.Name):
            out.add(t.id)
        elif isinstance(t, ast.Tuple):
            for e in t.elts:
                if isinstance(e, ast.Name):
                    out.add(e.id)
        return out

    def _caught_names(self, node: ast.ExceptHandler) -> str:
        if node.type is None:
            return ""
        return ast.unparse(node.type) if hasattr(ast, "unparse") else "..."

    def _handles(self, body: list[ast.stmt]) -> bool:
        """True if the handler visibly deals with the error: re-raises,
        logs, or forwards it into a future."""
        for stmt in body:
            for node in ast.walk(stmt):
                if isinstance(node, ast.Raise):
                    return True
                if isinstance(node, ast.Call):
                    f = node.func
                    # Any logger-shaped method call counts (logger.warning,
                    # self._log.error, logging.getLogger(...).exception).
                    if isinstance(f, ast.Attribute) and f.attr in self._LOG_METHODS:
                        return True
                    # Forwarding the error into a future propagates it.
                    if isinstance(f, ast.Attribute) and f.attr == "set_exception":
                        return True
                    if dotted(f) in ("warnings.warn", "traceback.print_exc"):
                        return True
        return False


# ---------------------------------------------------------------------------
# no-wall-clock-in-actors
# ---------------------------------------------------------------------------


@register
class NoWallClockInActors(Rule):
    name = "no-wall-clock-in-actors"
    summary = (
        "in primary/, worker/, consensus/, executor/ and network/: direct "
        "wall-clock reads (time.time / time.monotonic / time.perf_counter "
        "/ loop.time()) bypass the injected clock (narwhal_tpu/clock.now) "
        "— under the simnet virtual-clock harness a single stray read "
        "mixes wall time into pacing deadlines and retry backoffs, "
        "breaking both determinism and the zero-wall-clock-wait property"
    )

    _SCOPED_DIRS = frozenset(
        {"primary", "worker", "consensus", "executor", "network"}
    )
    _TIME_FUNCS = frozenset(
        {
            "time.time",
            "time.monotonic",
            "time.perf_counter",
            "time.time_ns",
            "time.monotonic_ns",
            "time.perf_counter_ns",
        }
    )
    _LOOP_GETTERS = frozenset(
        {"asyncio.get_event_loop", "asyncio.get_running_loop"}
    )

    def check(self, mod: Module) -> Iterator[Finding]:
        if not in_dirs(mod, self._SCOPED_DIRS):
            return
        aliases = import_aliases(mod.tree)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            target = resolve(node.func, aliases)
            if target in self._TIME_FUNCS:
                yield self.finding(
                    mod,
                    node,
                    f"`{target}()` reads the wall clock directly — go "
                    "through the injected clock (narwhal_tpu.clock.now) so "
                    "simnet's virtual time stays sound",
                )
                continue
            # loop.time(): any `<x>.time()` where <x> is a loop-ish name or
            # a direct get_event_loop()/get_running_loop() call.
            if not (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "time"
            ):
                continue
            base = node.func.value
            loopish = (
                isinstance(base, ast.Name) and "loop" in base.id.lower()
            ) or (
                isinstance(base, ast.Call)
                and resolve(base.func, aliases) in self._LOOP_GETTERS
            )
            if loopish:
                yield self.finding(
                    mod,
                    node,
                    "`loop.time()` in an actor bypasses the injected clock "
                    "(narwhal_tpu.clock.now); the two agree at runtime but "
                    "only the clock seam keeps the discipline greppable "
                    "and simnet-sound",
                )


# ---------------------------------------------------------------------------
# no-untracked-jit
# ---------------------------------------------------------------------------


@register
class NoUntrackedJit(Rule):
    name = "no-untracked-jit"
    summary = (
        "in tpu/, every jit entry point must route through the shared "
        "kernel registry (kernel_registry.tracked_jit / .sharded): a raw "
        "jax.jit owns its own private compile cache, so two wrappers over "
        "the same kernel+mesh each pay the full multi-minute XLA compile "
        "— the MULTICHIP rc=124 failure class — and its compile wall is "
        "invisible to the registry's per-(kernel, mesh shape) accounting"
    )

    _JIT = {"jax.jit", "jit"}

    def check(self, mod: Module) -> Iterator[Finding]:
        if "tpu" not in PurePath(mod.rel).parts:
            return
        if mod.path.name == "kernel_registry.py":  # the sanctioned wrapper
            return
        aliases = import_aliases(mod.tree)
        for node in ast.walk(mod.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for deco in node.decorator_list:
                    site = deco
                    d = resolve(deco, aliases)
                    if isinstance(deco, ast.Call):
                        d = resolve(deco.func, aliases)
                        if (
                            d in ("partial", "functools.partial")
                            and deco.args
                            and resolve(deco.args[0], aliases) in self._JIT
                        ):
                            d = resolve(deco.args[0], aliases)
                    if d in self._JIT:
                        yield self.finding(
                            mod,
                            site,
                            f"`@{ast.unparse(deco)}` on `{node.name}` "
                            "bypasses the shared kernel registry — use "
                            "`@kernel_registry.tracked_jit` so the compile "
                            "is deduped and its wall is accounted per "
                            "(kernel, mesh shape)",
                        )
            elif isinstance(node, ast.Call):
                if resolve(node.func, aliases) in self._JIT:
                    yield self.finding(
                        mod,
                        node,
                        "`jax.jit(...)` called outside the kernel registry "
                        "— sharded/mesh variants must come from "
                        "`kernel_registry.sharded(...)` (one compile per "
                        "(kernel, mesh shape) per process), module-level "
                        "kernels from `@kernel_registry.tracked_jit`",
                    )


# ---------------------------------------------------------------------------
# no-per-item-cert-verify
# ---------------------------------------------------------------------------


@register
class NoPerItemCertVerify(Rule):
    name = "no-per-item-cert-verify"
    summary = (
        "in primary/ and consensus/, a Certificate.verify (or raw "
        "host_verify_aggregate) call site runs per-certificate host crypto "
        "inline; certificates must ride the batched verifier API — the "
        "crypto pool's verify/verify_aggregate lanes or "
        "types.host_batch_verify_aggregates — so signature work amortizes "
        "one device dispatch / one bucket-method MSM per flush. The "
        "documented terminal fallbacks (no pool configured) carry a "
        "justified `# lint: allow(no-per-item-cert-verify)`"
    )

    _SCOPED_DIRS = frozenset({"primary", "consensus"})
    # Receiver-name heuristic for certificate-shaped objects; header.verify
    # and vote.verify never match (their per-item checks ARE the batched
    # stage's structural half).
    _CERT_METHODS = {"verify"}

    def check(self, mod: Module) -> Iterator[Finding]:
        if not in_dirs(mod, self._SCOPED_DIRS):
            return
        aliases = import_aliases(mod.tree)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            target = resolve(node.func, aliases)
            if target is not None and (
                target == "host_verify_aggregate"
                or target.endswith(".host_verify_aggregate")
            ):
                yield self.finding(
                    mod,
                    node,
                    "`host_verify_aggregate(...)` is the per-certificate "
                    "naive reference — dispatch proof groups through "
                    "`host_batch_verify_aggregates` (or the crypto pool's "
                    "verify_aggregate lane) so one MSM serves the flush",
                )
                continue
            if not (
                isinstance(node.func, ast.Attribute)
                and node.func.attr in self._CERT_METHODS
            ):
                continue
            recv = dotted(node.func.value)
            # Only the FINAL segment names the receiver: `cert.verify` is a
            # certificate check, `cert.header.verify` is the header's.
            if recv is None or "cert" not in recv.split(".")[-1].lower():
                continue
            yield self.finding(
                mod,
                node,
                f"`{recv}.{node.func.attr}(...)` verifies one certificate "
                "inline on the host — route it through the batched "
                "verifier API (verifier stage / crypto pool "
                "verify_aggregate), or justify a documented no-pool "
                "fallback with `# lint: allow(no-per-item-cert-verify)`",
            )


# ---------------------------------------------------------------------------
# metric-naming
# ---------------------------------------------------------------------------


@register
class MetricNaming(Rule):
    name = "metric-naming"
    summary = (
        "registry.counter/gauge/histogram names must follow "
        "<subsystem>_<name>[_<unit>]: snake_case, a known subsystem prefix, "
        "and a unit suffix on histograms — the checked-in metrics catalog "
        "(tools/metrics_catalog.json) and every dashboard key on this "
        "grammar, so a drive-by name invents a subsystem or loses its unit "
        "silently"
    )

    _METHODS = frozenset({"counter", "gauge", "histogram"})
    # "rpc" is the request layer above the wire (rpc_requests_failed_total).
    _SUBSYSTEMS = frozenset(
        {"consensus", "executor", "node", "primary", "rpc", "storage",
         "telemetry", "wire", "worker"}
    )
    # Histogram units in use; 'size'/'certificate' are count-like units
    # (created_batch_size, fetch_rpcs_per_certificate).
    _UNITS = frozenset({"seconds", "bytes", "size", "certificate"})
    _NAME_RE = re.compile(r"^[a-z][a-z0-9]*(_[a-z0-9]+)*$")

    def check(self, mod: Module) -> Iterator[Finding]:
        for node in ast.walk(mod.tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in self._METHODS
                and node.args
            ):
                continue
            first = node.args[0]
            # Computed names (the f-string channel-depth gauges built by
            # metered_channel) are covered by their own construction seam.
            if not (isinstance(first, ast.Constant) and isinstance(first.value, str)):
                continue
            name = first.value
            if not self._NAME_RE.match(name):
                yield self.finding(
                    mod,
                    node,
                    f"metric name {name!r} is not snake_case "
                    "(lowercase segments joined by single underscores)",
                )
                continue
            subsystem = name.split("_", 1)[0]
            if subsystem not in self._SUBSYSTEMS:
                yield self.finding(
                    mod,
                    node,
                    f"metric name {name!r} starts with unknown subsystem "
                    f"{subsystem!r}; use one of "
                    f"{'/'.join(sorted(self._SUBSYSTEMS))} (or extend the "
                    "lint's subsystem set deliberately)",
                )
                continue
            if (
                node.func.attr == "histogram"
                and name.rsplit("_", 1)[-1] not in self._UNITS
            ):
                yield self.finding(
                    mod,
                    node,
                    f"histogram {name!r} must end in a unit suffix "
                    f"({'/'.join(sorted(self._UNITS))}) so readers know "
                    "what the buckets measure",
                )


# ---------------------------------------------------------------------------
# no-direct-peer-connection
# ---------------------------------------------------------------------------


@register
class NoDirectPeerConnection(Rule):
    name = "no-direct-peer-connection"
    summary = (
        "in primary/, worker/ and executor/, peer connections must go "
        "through the node's LanePool (NetworkClient.peer routes committee "
        "addresses onto the one pooled link per peer pair): a direct "
        "transport.open_connection / asyncio.open_connection or a "
        "hand-built PeerClient(...) opens a dedicated socket per call "
        "site, quietly re-growing the O(N^2*(1+W)) mesh the pool "
        "collapsed — the socket wall an N=100 committee died on"
    )

    _SCOPED_DIRS = frozenset({"primary", "worker", "executor"})

    def check(self, mod: Module) -> Iterator[Finding]:
        if not in_dirs(mod, self._SCOPED_DIRS):
            return
        aliases = import_aliases(mod.tree)
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            resolved = resolve(node.func, aliases)
            if resolved is None:
                continue
            leaf = resolved.rsplit(".", 1)[-1]
            if leaf == "open_connection":
                yield self.finding(
                    mod,
                    node,
                    f"direct socket dial `{dotted(node.func)}(...)`: peer "
                    "connections belong to the LanePool (one multiplexed "
                    "link per peer pair) — use NetworkClient.peer / "
                    "pool.link_for instead of opening a dedicated stream",
                )
            elif leaf == "PeerClient":
                yield self.finding(
                    mod,
                    node,
                    f"hand-built `{dotted(node.func)}(...)`: construct "
                    "peers via NetworkClient.peer so committee addresses "
                    "ride the pooled lane (PeerClient is the pool's "
                    "internal legacy fallback, not an application API)",
                )
