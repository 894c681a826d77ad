"""graftlint rules: the serving-invariant registry.

Four rules are straight ports of the tests/test_layering.py AST lints
(that file is now a thin bridge over this registry); the rest encode
the threading/clock/jit/exception contracts that previously lived
only in review comments. Each rule names the contract it enforces in
`rationale` so a finding points at the why.

Shared-helper functions (host_copy_sites, class_alloc_sites,
raw_mesh_uses) are module-level so the legacy test bridge can keep
its vacuity guards against the same walkers the rules use.
"""

import ast
from typing import Dict, FrozenSet, Iterator, List, Optional, Tuple

from dlrover_tpu.analysis.core import (
    CRITICAL,
    WARNING,
    Finding,
    Rule,
    SourceFile,
)

SERVING_PREFIX = "dlrover_tpu/serving/"
DECODE_FILE = "dlrover_tpu/models/decode.py"
ENGINE_FILE = SERVING_PREFIX + "engine.py"
PAGED_KV_FILE = SERVING_PREFIX + "paged_kv.py"
HANDOFF_FILE = SERVING_PREFIX + "handoff.py"
KV_TIER_FILE = SERVING_PREFIX + "kv_tier.py"


def _in_serving(src: SourceFile) -> bool:
    # substring, not prefix: a file handed to the CLI by absolute
    # path still gets the serving rules applied
    return SERVING_PREFIX in src.rel


def _matches_file(rel: str, key: str) -> bool:
    return rel == key or rel.endswith("/" + key)


def _file_config(rel: str, table: Dict[str, FrozenSet[str]]):
    for key, value in table.items():
        if _matches_file(rel, key):
            return value
    return None


def walk_with_owner(
    tree: ast.AST,
) -> Iterator[Tuple[ast.AST, Optional[str]]]:
    """(node, enclosing-function-name) pairs; owner is None at module
    and class scope (i.e. code that RUNS at import time — a lambda
    body counts as deferred, so lambdas become owners too)."""

    def visit(node, owner):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            owner = node.name
        elif isinstance(node, ast.Lambda):
            owner = "<lambda>"
        yield node, owner
        for child in ast.iter_child_nodes(node):
            yield from visit(child, owner)

    yield from visit(tree, None)


# ---------------------------------------------------------------------------
# LAYER-001: serving/ never imports dlrover_tpu.rl


_FORBIDDEN_IMPORT = "dlrover_tpu.rl"


def rl_import_uses(tree: ast.AST) -> List[Tuple[int, str]]:
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                name = alias.name
                if name == _FORBIDDEN_IMPORT or name.startswith(
                    _FORBIDDEN_IMPORT + "."
                ):
                    out.append((node.lineno, f"import {name}"))
        elif isinstance(node, ast.ImportFrom):
            # level>0 is a relative import inside serving/ — it cannot
            # reach dlrover_tpu.rl without an absolute name
            mod = node.module or ""
            if node.level == 0 and (
                mod == _FORBIDDEN_IMPORT
                or mod.startswith(_FORBIDDEN_IMPORT + ".")
            ):
                out.append((node.lineno, f"from {mod} import ..."))
            elif node.level == 0 and mod == "dlrover_tpu":
                for alias in node.names:
                    if alias.name == "rl":
                        out.append(
                            (node.lineno, "from dlrover_tpu import rl")
                        )
    return out


class RlImportRule(Rule):
    id = "LAYER-001"
    severity = CRITICAL
    title = "serving/ must not import dlrover_tpu.rl"
    rationale = (
        "DEVIATIONS §5: the dependency is one-way — rl/serve.py "
        "imports the serving engine, never the reverse, so the "
        "serving stack stays usable without the RL stack."
    )

    def applies(self, src: SourceFile) -> bool:
        return _in_serving(src)

    def check(self, src: SourceFile) -> List[Finding]:
        return [
            self.finding(src, lineno, what)
            for lineno, what in rl_import_uses(src.tree)
        ]


# ---------------------------------------------------------------------------
# HOST-001: host materialization only in designated fetch helpers


# calls that synchronously materialize a device array on host
HOST_COPY_CALLS = {
    ("np", "array"),
    ("np", "asarray"),
    ("np", "copy"),
    ("numpy", "array"),
    ("numpy", "asarray"),
    ("numpy", "copy"),
    ("jax", "device_get"),
}

# functions allowed to materialize host arrays, per file. engine.py:
# the ONE designated device fetch point plus the host-data paths
# (prompt normalization at submit, output-list conversion at
# retire/drain, prompt-folding at preemption — all of which only touch
# host-resident numpy data, never a dispatch result). `_admit` is NOT
# among them: its allowance once hid `np.asarray(sub)` of a key split
# on the device, a fetch behind the admission's own prefill
# (DEVIATIONS §9); the key is split on the host now and an admission
# converts nothing. decode.py and paged_kv.py currently have
# NO host-copy sites at all; the empty allowlists freeze that.
HOST_COPY_ALLOWED: Dict[str, FrozenSet[str]] = {
    ENGINE_FILE: frozenset(
        {
            "_to_host",
            "submit",
            "retire",
            "generate_all",
            "_preempt_slot",
        }
    ),
    DECODE_FILE: frozenset(),
    PAGED_KV_FILE: frozenset(),
    # handoff.py: the host-transport bounce is the module's one D2H
    # point; export_run's np.asarray only copies the host-resident
    # prompt (engine.py's submit/_admit category), never KV
    HANDOFF_FILE: frozenset({"_host_bounce", "export_run"}),
    # kv_tier.py: _fetch is the tier's single blocking-fetch site —
    # demotion staging goes through it after the async D2H copies
    # were started (same discipline as engine._to_host)
    KV_TIER_FILE: frozenset({"_fetch"}),
}


def host_copy_sites(
    tree: ast.AST,
) -> List[Tuple[int, str, Optional[str]]]:
    """(lineno, call, enclosing-function-name) for every potentially
    blocking host materialization; owner is None at module scope."""
    out = []
    for node, owner in walk_with_owner(tree):
        if isinstance(node, ast.Call):
            f = node.func
            if (
                isinstance(f, ast.Attribute)
                and isinstance(f.value, ast.Name)
                and (f.value.id, f.attr) in HOST_COPY_CALLS
            ):
                out.append(
                    (node.lineno, f"{f.value.id}.{f.attr}", owner)
                )
    return out


class HostCopyRule(Rule):
    id = "HOST-001"
    severity = CRITICAL
    title = "host copies only in designated fetch helpers"
    rationale = (
        "DEVIATIONS §9: the async dispatch design depends on the "
        "step hot path never issuing a fresh blocking device->host "
        "copy — a stray np.array(<jax array>) silently re-serializes "
        "host and device."
    )

    def applies(self, src: SourceFile) -> bool:
        return _file_config(src.rel, HOST_COPY_ALLOWED) is not None

    def check(self, src: SourceFile) -> List[Finding]:
        allowed = _file_config(src.rel, HOST_COPY_ALLOWED)
        return [
            self.finding(
                src,
                lineno,
                f"{call} in {owner or '<module>'}() — host "
                f"materialization allowed only in "
                f"{sorted(allowed) or 'nothing in this file'}",
            )
            for lineno, call, owner in host_copy_sites(src.tree)
            if owner not in allowed
        ]


# ---------------------------------------------------------------------------
# ALLOC-001: no per-step device allocation in engine-class methods


DEVICE_ALLOC_ALLOWED = frozenset({"__init__", "reset"})

DEVICE_ALLOC_CALLS = {
    ("jnp", "zeros"),
    ("jnp", "ones"),
    ("jnp", "full"),
    ("jnp", "empty"),
    ("jnp", "arange"),
    ("jnp", "zeros_like"),
    ("jnp", "ones_like"),
    ("jnp", "full_like"),
}

# bulk device-state constructors (engine.py top-level helpers)
DEVICE_ALLOC_NAMES = {"init_kv_cache", "init_page_pool"}

_ALLOC_FILES = frozenset({ENGINE_FILE, PAGED_KV_FILE, DECODE_FILE})


def class_alloc_sites(
    tree: ast.AST, class_name: Optional[str] = None
) -> List[Tuple[int, str, str, str]]:
    """(lineno, call, method, class) for every eager device
    allocation inside class methods (module-level functions — the jit
    program builders — are intentionally out of scope: jnp calls
    there run under trace and compile into the program instead of
    allocating eagerly)."""
    out = []
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        if class_name is not None and cls.name != class_name:
            continue
        for method in cls.body:
            if not isinstance(
                method, (ast.FunctionDef, ast.AsyncFunctionDef)
            ):
                continue
            for node in ast.walk(method):
                if not isinstance(node, ast.Call):
                    continue
                f = node.func
                if (
                    isinstance(f, ast.Attribute)
                    and isinstance(f.value, ast.Name)
                    and (f.value.id, f.attr) in DEVICE_ALLOC_CALLS
                ):
                    out.append(
                        (
                            node.lineno,
                            f"{f.value.id}.{f.attr}",
                            method.name,
                            cls.name,
                        )
                    )
                elif (
                    isinstance(f, ast.Name)
                    and f.id in DEVICE_ALLOC_NAMES
                ):
                    out.append(
                        (node.lineno, f.id, method.name, cls.name)
                    )
    return out


class DeviceAllocRule(Rule):
    id = "ALLOC-001"
    severity = CRITICAL
    title = "no device allocation outside __init__/reset"
    rationale = (
        "DEVIATIONS §10: page tables, the page pool, and the slot "
        "bank are built ONCE and thereafter updated through donated "
        "jitted programs; a stray jnp.zeros(...) in an engine method "
        "allocates + transfers on every call."
    )

    def applies(self, src: SourceFile) -> bool:
        return any(
            _matches_file(src.rel, key) for key in _ALLOC_FILES
        )

    def check(self, src: SourceFile) -> List[Finding]:
        return [
            self.finding(
                src,
                lineno,
                f"{call} in {cls}.{method}() — device allocation "
                f"allowed only in {sorted(DEVICE_ALLOC_ALLOWED)}",
            )
            for lineno, call, method, cls in class_alloc_sites(
                src.tree
            )
            if method not in DEVICE_ALLOC_ALLOWED
        ]


# ---------------------------------------------------------------------------
# MESH-001: serving/ never constructs a raw jax.sharding.Mesh


def raw_mesh_uses(tree: ast.AST) -> List[Tuple[int, str]]:
    """(lineno, what) for every direct jax.sharding.Mesh reference:
    `from jax.sharding import Mesh`, `jax.sharding.Mesh(...)`, or an
    aliased `sharding.Mesh(...)`."""
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if node.level == 0 and mod == "jax.sharding":
                for alias in node.names:
                    if alias.name == "Mesh":
                        out.append(
                            (
                                node.lineno,
                                "from jax.sharding import Mesh",
                            )
                        )
        elif isinstance(node, ast.Attribute) and node.attr == "Mesh":
            v = node.value
            # jax.sharding.Mesh  /  sharding.Mesh
            if (
                isinstance(v, ast.Attribute)
                and v.attr == "sharding"
                and isinstance(v.value, ast.Name)
                and v.value.id == "jax"
            ) or (isinstance(v, ast.Name) and v.id == "sharding"):
                out.append((node.lineno, ast.unparse(node)))
    return out


class RawMeshRule(Rule):
    id = "MESH-001"
    severity = CRITICAL
    title = "serving/ must not construct jax.sharding.Mesh"
    rationale = (
        "DEVIATIONS §11: the ONE mesh factory is parallel/mesh.py "
        "(serving_mesh) — it owns axis naming, device selection, and "
        "divisibility validation; a raw Mesh would mint an axis-name "
        "convention decode.py's PartitionSpecs silently don't match."
    )

    def applies(self, src: SourceFile) -> bool:
        return _in_serving(src)

    def check(self, src: SourceFile) -> List[Finding]:
        return [
            self.finding(src, lineno, what)
            for lineno, what in raw_mesh_uses(src.tree)
        ]


# ---------------------------------------------------------------------------
# LOCK-001: lock discipline for thread-spawning classes


# constructing any of these inside a class makes it a concurrency
# participant that must declare its guarded-field set
_THREADING_FACTORIES = frozenset(
    {"Thread", "Lock", "RLock", "Condition"}
)

_LOCK_ATTRS = frozenset({"_lock", "_cond"})


def _creates_threading(cls: ast.ClassDef) -> Optional[int]:
    for node in ast.walk(cls):
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id == "threading"
            and node.func.attr in _THREADING_FACTORIES
        ):
            return node.lineno
    return None


def _declared_guarded_fields(
    cls: ast.ClassDef,
) -> Optional[FrozenSet[str]]:
    """Parse a class-body `GUARDED_FIELDS = frozenset({...})` (or a
    bare set literal). None when not declared."""
    for stmt in cls.body:
        if not isinstance(stmt, ast.Assign):
            continue
        if not any(
            isinstance(t, ast.Name) and t.id == "GUARDED_FIELDS"
            for t in stmt.targets
        ):
            continue
        value = stmt.value
        if (
            isinstance(value, ast.Call)
            and isinstance(value.func, ast.Name)
            and value.func.id == "frozenset"
        ):
            if not value.args:
                return frozenset()
            value = value.args[0]
        names = set()
        if isinstance(value, (ast.Set, ast.Tuple, ast.List)):
            for el in value.elts:
                if isinstance(el, ast.Constant) and isinstance(
                    el.value, str
                ):
                    names.add(el.value)
        return frozenset(names)
    return None


def _is_self_lock(expr: ast.AST) -> bool:
    return (
        isinstance(expr, ast.Attribute)
        and isinstance(expr.value, ast.Name)
        and expr.value.id == "self"
        and expr.attr in _LOCK_ATTRS
    )


def _unguarded_accesses(
    method: ast.AST, guarded: FrozenSet[str]
) -> List[Tuple[int, str]]:
    """(lineno, field) for every `self.<guarded>` access not lexically
    inside a `with self._lock` / `with self._cond` block."""
    out = []

    def visit(node, locked):
        if isinstance(node, ast.With):
            if any(
                _is_self_lock(item.context_expr)
                for item in node.items
            ):
                locked = True
        if (
            isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"
            and node.attr in guarded
            and not locked
        ):
            out.append((node.lineno, node.attr))
        for child in ast.iter_child_nodes(node):
            visit(child, locked)

    visit(method, False)
    return out


class LockDisciplineRule(Rule):
    id = "LOCK-001"
    severity = CRITICAL
    title = "guarded fields accessed only under the lock"
    rationale = (
        "The scheduler/pool/gateway/metrics threads share state "
        "across the request path, the pump loop, and the health "
        "loop; every cross-thread field must be declared in the "
        "class's GUARDED_FIELDS and touched only inside `with "
        "self._lock`/`self._cond`, in __init__, or in a "
        "`*_locked`-convention method (called with the lock held)."
    )

    def applies(self, src: SourceFile) -> bool:
        return _in_serving(src)

    def check(self, src: SourceFile) -> List[Finding]:
        findings = []
        for cls in ast.walk(src.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            lineno = _creates_threading(cls)
            if lineno is None:
                continue
            guarded = _declared_guarded_fields(cls)
            if guarded is None:
                findings.append(
                    self.finding(
                        src,
                        cls.lineno,
                        f"class {cls.name} creates threading "
                        "primitives but declares no GUARDED_FIELDS "
                        "(= frozenset of cross-thread field names)",
                    )
                )
                continue
            for method in cls.body:
                if not isinstance(
                    method, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    continue
                if method.name == "__init__" or method.name.endswith(
                    "_locked"
                ):
                    continue
                for line, field in _unguarded_accesses(
                    method, guarded
                ):
                    findings.append(
                        self.finding(
                            src,
                            line,
                            f"{cls.name}.{method.name}() touches "
                            f"guarded field self.{field} outside "
                            "`with self._lock`/`self._cond` (rename "
                            "to *_locked if callers hold the lock)",
                        )
                    )
        return findings


# ---------------------------------------------------------------------------
# CLOCK-001: deadline/latency arithmetic never uses the wall clock


class ClockDisciplineRule(Rule):
    id = "CLOCK-001"
    severity = CRITICAL
    title = "serving/ uses monotonic (or injected) clocks"
    rationale = (
        "Deadlines, backoffs, and latency windows must survive NTP "
        "steps: use the injected clock or time.monotonic(). "
        "time.time() is allowed only for wall-clock telemetry "
        "(heartbeat/hint `ts` fields read by master-side staleness "
        "checks) behind an explicit pragma."
    )

    def applies(self, src: SourceFile) -> bool:
        return _in_serving(src)

    def check(self, src: SourceFile) -> List[Finding]:
        out = []
        for node in ast.walk(src.tree):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "time"
                and node.func.attr == "time"
            ):
                out.append(
                    self.finding(
                        src,
                        node.lineno,
                        "time.time() — use the injected clock or "
                        "time.monotonic() for anything fed into "
                        "deadline/backoff/latency arithmetic",
                    )
                )
        return out


# ---------------------------------------------------------------------------
# JIT-001 / JIT-002 / JIT-003: jit hygiene


def _is_jit_expr(expr: ast.AST) -> bool:
    if isinstance(expr, ast.Name) and expr.id == "jit":
        return True
    return (
        isinstance(expr, ast.Attribute)
        and expr.attr == "jit"
        and isinstance(expr.value, ast.Name)
        and expr.value.id == "jax"
    )


def _jit_decorated(node) -> bool:
    for dec in node.decorator_list:
        if _is_jit_expr(dec):
            return True
        if isinstance(dec, ast.Call):
            if _is_jit_expr(dec.func):
                return True
            # @partial(jax.jit, ...) / @functools.partial(jax.jit, ..)
            f = dec.func
            is_partial = (
                isinstance(f, ast.Name) and f.id == "partial"
            ) or (isinstance(f, ast.Attribute) and f.attr == "partial")
            if is_partial and dec.args and _is_jit_expr(dec.args[0]):
                return True
    return False


class JitSelfCaptureRule(Rule):
    id = "JIT-001"
    severity = CRITICAL
    title = "no jax.jit over closures capturing self"
    rationale = (
        "A jitted function that closes over `self` keys its trace "
        "cache on the bound instance: every engine restart retraces "
        "every program, silently defeating the module-level "
        "_CHUNK/_ADMIT/_SPEC program caches (DEVIATIONS §9)."
    )

    def applies(self, src: SourceFile) -> bool:
        return _in_serving(src) or _matches_file(
            src.rel, DECODE_FILE
        )

    def check(self, src: SourceFile) -> List[Finding]:
        out = []
        for node in ast.walk(src.tree):
            body = None
            if isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef)
            ) and _jit_decorated(node):
                body = node.body
                where = f"jitted {node.name}()"
            elif (
                isinstance(node, ast.Call)
                and _is_jit_expr(node.func)
                and node.args
                and isinstance(node.args[0], ast.Lambda)
            ):
                body = [node.args[0].body]
                where = "jax.jit(<lambda>)"
            if body is None:
                continue
            for stmt in body:
                for sub in ast.walk(stmt):
                    if (
                        isinstance(sub, ast.Name)
                        and sub.id == "self"
                    ):
                        out.append(
                            self.finding(
                                src,
                                sub.lineno,
                                f"{where} references `self` — trace "
                                "cache becomes per-instance; pass "
                                "state as arguments instead",
                            )
                        )
                        break
        return out


class EagerJnpImportRule(Rule):
    id = "JIT-002"
    severity = WARNING
    title = "no eager jnp calls at module import in serving/"
    rationale = (
        "A module-scope jnp call allocates on (and may initialize) "
        "the backend at import time — serving modules must stay "
        "importable without a device (the CLI, the gateway tests, "
        "and the analysis pass all rely on cheap imports)."
    )

    def applies(self, src: SourceFile) -> bool:
        return _in_serving(src)

    def check(self, src: SourceFile) -> List[Finding]:
        out = []
        for node, owner in walk_with_owner(src.tree):
            if (
                owner is None
                and isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "jnp"
            ):
                out.append(
                    self.finding(
                        src,
                        node.lineno,
                        f"eager jnp.{node.func.attr}(...) at module "
                        "scope runs at import time",
                    )
                )
        return out


_UNHASHABLE_DISPLAYS = (
    ast.List,
    ast.Dict,
    ast.Set,
    ast.ListComp,
    ast.SetComp,
    ast.DictComp,
    ast.GeneratorExp,
)


class ProgramCacheKeyRule(Rule):
    id = "JIT-003"
    severity = WARNING
    title = "program-cache keys are hashable tuple literals"
    rationale = (
        "_cached_program silently falls back to per-instance builds "
        "on an unhashable key (TypeError path) — a list/dict/set in "
        "the key would disable program sharing without any failure."
    )

    def applies(self, src: SourceFile) -> bool:
        return _in_serving(src)

    def check(self, src: SourceFile) -> List[Finding]:
        out = []
        for node in ast.walk(src.tree):
            if not (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id == "_cached_program"
            ):
                continue
            if len(node.args) < 2:
                continue
            key = node.args[1]
            if not isinstance(key, ast.Tuple):
                out.append(
                    self.finding(
                        src,
                        key.lineno,
                        "_cached_program key must be a tuple "
                        "literal (got "
                        f"{type(key).__name__})",
                    )
                )
                continue
            for sub in ast.walk(key):
                if isinstance(sub, _UNHASHABLE_DISPLAYS):
                    out.append(
                        self.finding(
                            src,
                            sub.lineno,
                            "_cached_program key contains an "
                            f"unhashable {type(sub).__name__} "
                            "display — the cache would silently "
                            "fall back to per-instance builds",
                        )
                    )
                    break
        return out


# ---------------------------------------------------------------------------
# EXC-001: broad excepts must re-raise, log, or carry a pragma


_LOG_METHODS = frozenset(
    {
        "exception",
        "warning",
        "error",
        "info",
        "debug",
        "critical",
        "log",
    }
)


def _is_broad_handler(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:  # bare except
        return True
    if isinstance(t, ast.Name) and t.id in (
        "Exception",
        "BaseException",
    ):
        return True
    if isinstance(t, ast.Tuple):
        return any(
            isinstance(el, ast.Name)
            and el.id in ("Exception", "BaseException")
            for el in t.elts
        )
    return False


def _handler_disposes(handler: ast.ExceptHandler) -> bool:
    for node in ast.walk(handler):
        if isinstance(node, ast.Raise):
            return True
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in _LOG_METHODS
        ):
            return True
    return False


class BroadExceptRule(Rule):
    id = "EXC-001"
    severity = WARNING
    title = "broad excepts in serving/ must re-raise or log"
    rationale = (
        "A silent `except Exception: pass/continue` in the serving "
        "path swallows real failures (XLA errors, KV outages) "
        "indistinguishably from the faults it meant to tolerate — "
        "the crash-safety story (DEVIATIONS §8) depends on failures "
        "being observed."
    )

    def applies(self, src: SourceFile) -> bool:
        return _in_serving(src)

    def check(self, src: SourceFile) -> List[Finding]:
        return [
            self.finding(
                src,
                node.lineno,
                "broad except neither re-raises nor logs — swallow "
                "sites must be observable (or pragma'd with a "
                "reason)",
            )
            for node in ast.walk(src.tree)
            if isinstance(node, ast.ExceptHandler)
            and _is_broad_handler(node)
            and not _handler_disposes(node)
        ]


# ---------------------------------------------------------------------------
# KERNEL-001: Pallas/shard_map hygiene


OPS_PREFIX = "dlrover_tpu/ops/"
PARALLEL_PREFIX = "dlrover_tpu/parallel/"


def _in_ops(src: SourceFile) -> bool:
    return OPS_PREFIX in src.rel


def _in_parallel(src: SourceFile) -> bool:
    return PARALLEL_PREFIX in src.rel


def pallas_call_sites(
    tree: ast.AST,
) -> List[Tuple[int, Optional[str]]]:
    """(lineno, unparsed-interpret-kwarg-or-None) for every
    `pallas_call(...)` / `pl.pallas_call(...)` invocation."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        named = isinstance(f, ast.Name) and f.id == "pallas_call"
        attred = (
            isinstance(f, ast.Attribute) and f.attr == "pallas_call"
        )
        if not (named or attred):
            continue
        interp = None
        for kw in node.keywords:
            if kw.arg == "interpret":
                interp = ast.unparse(kw.value)
        out.append((node.lineno, interp))
    return out


def shard_map_uses(tree: ast.AST) -> List[Tuple[int, str]]:
    """(lineno, what) for every shard_map import or call: `from jax
    import shard_map`, `from jax.experimental.shard_map import ...`,
    `shard_map(...)`, or any `<x>.shard_map(...)`."""
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if node.level == 0 and mod == "jax.experimental.shard_map":
                out.append(
                    (node.lineno, f"from {mod} import ...")
                )
            elif node.level == 0 and mod == "jax":
                for alias in node.names:
                    if alias.name == "shard_map":
                        out.append(
                            (
                                node.lineno,
                                "from jax import shard_map",
                            )
                        )
        elif isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Name) and f.id == "shard_map":
                out.append((node.lineno, "shard_map(...)"))
            elif (
                isinstance(f, ast.Attribute)
                and f.attr == "shard_map"
            ):
                out.append(
                    (node.lineno, f"{ast.unparse(f)}(...)")
                )
    return out


class KernelHygieneRule(Rule):
    id = "KERNEL-001"
    severity = CRITICAL
    title = "Pallas kernels gate interpret; shard_map stays in ops//parallel/"
    rationale = (
        "DEVIATIONS §13: every pallas_call must pass "
        "interpret=_interpret() so the same kernel body runs "
        "compiled on TPU and interpreted in the CPU parity tests — "
        "a hardcoded interpret flag silently forks the two. And "
        "shard_map is a kernel/collective implementation detail: "
        "models and serving consume it only through the ops/ entry "
        "points (sharded_flash_attention, paged_attention) and "
        "parallel/ wrappers, so the no-collectives-in-kernel-body "
        "contract stays auditable in one place."
    )

    def applies(self, src: SourceFile) -> bool:
        # every package file: ops/ gets the interpret check, files
        # outside ops//parallel/ get the shard_map containment check
        return True

    def check(self, src: SourceFile) -> List[Finding]:
        findings = []
        if _in_ops(src):
            for lineno, interp in pallas_call_sites(src.tree):
                if interp is None or not interp.endswith(
                    "_interpret()"
                ):
                    findings.append(
                        self.finding(
                            src,
                            lineno,
                            "pallas_call must pass "
                            "interpret=_interpret() (got "
                            f"interpret={interp})",
                        )
                    )
        if not (_in_ops(src) or _in_parallel(src)):
            for lineno, what in shard_map_uses(src.tree):
                findings.append(
                    self.finding(
                        src,
                        lineno,
                        f"{what} — shard_map may only be "
                        "imported/constructed under ops/ or "
                        "parallel/; call the ops/ entry points "
                        "instead",
                    )
                )
        return findings


# ---------------------------------------------------------------------------
# HANDOFF-001: page-run adoption only through the install entry point


# files that ARE the install path: the allocator (owns adopt()) and
# the handoff module (the one caller)
_ADOPTION_EXEMPT = (PAGED_KV_FILE, HANDOFF_FILE)

# allocator internals no other serving file may reach into — writing
# either directly would mint pages the leak check can't see
_ALLOCATOR_PRIVATE = frozenset({"_refs", "_free"})


def adoption_sites(tree: ast.AST) -> List[Tuple[int, str]]:
    """(lineno, what) for every `<expr>.adopt(...)` call and every
    non-self access to a private allocator field."""
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr == "adopt":
                out.append((node.lineno, f"{ast.unparse(f)}(...)"))
        elif (
            isinstance(node, ast.Attribute)
            and node.attr in _ALLOCATOR_PRIVATE
            and not (
                isinstance(node.value, ast.Name)
                and node.value.id == "self"
            )
        ):
            out.append((node.lineno, ast.unparse(node)))
    return out


class HandoffAdoptionRule(Rule):
    id = "HANDOFF-001"
    severity = CRITICAL
    title = "page-run adoption only through the allocator entry point"
    rationale = (
        "DEVIATIONS §14: cross-replica handoff installs shipped page "
        "runs through PageAllocator.adopt — the same refcount-1 "
        "table-write install the prefix pool uses, so the one-CoW-"
        "site invariant and the zero-leak check() stay true. An "
        "ad-hoc adopt() call or a poke at the allocator's _refs/_free "
        "from anywhere else mints pages the accounting can't see."
    )

    def applies(self, src: SourceFile) -> bool:
        return _in_serving(src) and not any(
            _matches_file(src.rel, key) for key in _ADOPTION_EXEMPT
        )

    def check(self, src: SourceFile) -> List[Finding]:
        return [
            self.finding(
                src,
                lineno,
                f"{what} — page adoption and allocator internals "
                "belong to paged_kv.py/handoff.py only",
            )
            for lineno, what in adoption_sites(src.tree)
        ]


# ---------------------------------------------------------------------------
# ELASTIC-001: resharding only through designated entry points


ELASTIC_FILE = SERVING_PREFIX + "elastic.py"

# resharding primitives: placing arrays onto a (new) sharding, laying
# a param tree out under a mesh, or minting a serving mesh slice
_RESHARD_CALLS = frozenset({"device_put", "serving_mesh", "shard_tree"})

# functions allowed to call them, per serving file. engine.py: mesh
# construction in __init__ plus the three placement helpers every
# build/rebuild routes through; handoff.py: adoption places shipped
# KV onto the TARGET engine's existing sharding (a transfer, not a
# resize). Serving files not listed allow nothing. elastic.py is
# exempt wholesale (see applies): the resize choreography IS the one
# sanctioned out-of-construction resharding site.
_RESHARD_ALLOWED: Dict[str, FrozenSet[str]] = {
    ENGINE_FILE: frozenset(
        {"__init__", "_shard_params", "_shard_bank", "_replicate"}
    ),
    HANDOFF_FILE: frozenset({"adopt_into_slot"}),
    # kv_tier.py: promotion places host-tier bytes back onto the
    # POOL's existing sharding (a transfer, not a resize — the same
    # category as handoff adoption)
    KV_TIER_FILE: frozenset({"upload_row", "upload_pages"}),
}


def reshard_sites(
    tree: ast.AST,
) -> List[Tuple[int, str, Optional[str]]]:
    """(lineno, call, enclosing-function-name) for every resharding
    primitive call: bare `device_put`/`serving_mesh`/`shard_tree` or
    any attribute spelling (jax.device_put, mesh_mod.serving_mesh)."""
    out = []
    for node, owner in walk_with_owner(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = None
        if isinstance(f, ast.Name) and f.id in _RESHARD_CALLS:
            name = f.id
        elif isinstance(f, ast.Attribute) and f.attr in _RESHARD_CALLS:
            name = ast.unparse(f)
        if name is not None:
            out.append((node.lineno, name, owner))
    return out


class ElasticReshardRule(Rule):
    id = "ELASTIC-001"
    severity = CRITICAL
    title = "resharding only through designated entry points"
    rationale = (
        "DEVIATIONS §15: a live mesh resize must be one choreography "
        "— serving/elastic.py, built on parallel/mesh.py and "
        "parallel/sharding.py plus the engine's construction-time "
        "placement helpers. An ad-hoc device_put-onto-new-sharding "
        "in an engine method mints a placement the program caches "
        "(keyed on the mesh) never see, and a mesh minted outside "
        "the factory can violate the n_kv_heads % tp gate the "
        "factory validates."
    )

    def applies(self, src: SourceFile) -> bool:
        return _in_serving(src) and not _matches_file(
            src.rel, ELASTIC_FILE
        )

    def check(self, src: SourceFile) -> List[Finding]:
        allowed = _file_config(src.rel, _RESHARD_ALLOWED) or frozenset()
        return [
            self.finding(
                src,
                lineno,
                f"{call} in {owner or '<module>'}() — resharding "
                f"allowed only in "
                f"{sorted(allowed) or 'nothing in this file'}; route "
                "resizes through serving/elastic.py",
            )
            for lineno, call, owner in reshard_sites(src.tree)
            if owner not in allowed
        ]


# ---------------------------------------------------------------------------
# ADAPTER-001: adapter-bank allocation/eviction only in adapters.py


ADAPTERS_FILE = SERVING_PREFIX + "adapters.py"

# bank constructors/mutators owned by serving/adapters.py: building a
# fresh stacked bank, jit-scattering one slot of it, and the cache's
# private eviction/upload internals. The engine (and everything else)
# goes through DeviceAdapterCache.acquire/release/rebuild and reads
# .bank — never mints or pokes bank state itself.
_ADAPTER_BANK_CALLS = frozenset(
    {"init_adapter_bank", "_bank_slot_write", "_take_slot", "_upload"}
)

# cache internals no other serving file may reach into — mutating
# either directly desyncs the LRU order / pin counts from the device
# bank's slot contents
_ADAPTER_CACHE_PRIVATE = frozenset({"_resident", "_pins"})


def adapter_bank_sites(tree: ast.AST) -> List[Tuple[int, str]]:
    """(lineno, what) for every adapter-bank constructor/mutator call
    (bare name or any attribute spelling) and every non-self access to
    a private adapter-cache field."""
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            f = node.func
            if (
                isinstance(f, ast.Name)
                and f.id in _ADAPTER_BANK_CALLS
            ):
                out.append((node.lineno, f"{f.id}(...)"))
            elif (
                isinstance(f, ast.Attribute)
                and f.attr in _ADAPTER_BANK_CALLS
            ):
                out.append((node.lineno, f"{ast.unparse(f)}(...)"))
        elif (
            isinstance(node, ast.Attribute)
            and node.attr in _ADAPTER_CACHE_PRIVATE
            and not (
                isinstance(node.value, ast.Name)
                and node.value.id == "self"
            )
        ):
            out.append((node.lineno, ast.unparse(node)))
    return out


class AdapterBankRule(Rule):
    id = "ADAPTER-001"
    severity = CRITICAL
    title = "adapter-bank allocation/eviction only in adapters.py"
    rationale = (
        "DEVIATIONS §16: the stacked device adapter bank is built "
        "once and mutated only through the LRU cache's pinned-aware "
        "slot recycling in serving/adapters.py — slot indices live "
        "inside admitted requests' device state, so an ad-hoc bank "
        "build or slot write anywhere else can re-point a decoding "
        "request at another tenant's weights, and a poke at the "
        "cache's _resident/_pins desyncs eviction from the pins that "
        "make it safe."
    )

    def applies(self, src: SourceFile) -> bool:
        return _in_serving(src) and not _matches_file(
            src.rel, ADAPTERS_FILE
        )

    def check(self, src: SourceFile) -> List[Finding]:
        return [
            self.finding(
                src,
                lineno,
                f"{what} — adapter-bank construction and slot "
                "recycling belong to serving/adapters.py only; go "
                "through DeviceAdapterCache.acquire/release/rebuild",
            )
            for lineno, what in adapter_bank_sites(src.tree)
        ]


# ---------------------------------------------------------------------------
# ROUTE-001: fleet routing decisions only in replica.py + affinity.py


AFFINITY_FILE = SERVING_PREFIX + "affinity.py"
REPLICA_FILE = SERVING_PREFIX + "replica.py"
# kv_tier.py is exempt for digest CONSTRUCTION only: it keys demoted
# entries with prefix_digest_chain (the same digests the heartbeat
# advertises) but never reads the fleet map or ranks candidates
_ROUTING_EXEMPT = (REPLICA_FILE, AFFINITY_FILE, KV_TIER_FILE)

# the routing-decision API owned by serving/affinity.py: digest-map
# reads, candidate ranking, and digest-chain construction. Everything
# else observes routing through stats()/routing_stats() — it never
# ranks candidates or reads the map itself.
_ROUTING_CALLS = frozenset(
    {
        "match_depths",
        "affinity_order",
        "prefix_digest_chain",
        "cache_digests",
    }
)

# FleetDigestMap internals no other serving file may reach into —
# mutating either index directly desyncs digest→replica from
# replica→digest and mints routes update()/drop() can't retract
_DIGEST_MAP_PRIVATE = frozenset({"_by_digest", "_by_replica"})


def routing_sites(tree: ast.AST) -> List[Tuple[int, str]]:
    """(lineno, what) for every routing-decision call (bare name or
    any attribute spelling) and every non-self access to a private
    digest-map field."""
    out = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Name) and f.id in _ROUTING_CALLS:
                out.append((node.lineno, f"{f.id}(...)"))
            elif (
                isinstance(f, ast.Attribute)
                and f.attr in _ROUTING_CALLS
            ):
                out.append((node.lineno, f"{ast.unparse(f)}(...)"))
        elif (
            isinstance(node, ast.Attribute)
            and node.attr in _DIGEST_MAP_PRIVATE
            and not (
                isinstance(node.value, ast.Name)
                and node.value.id == "self"
            )
        ):
            out.append((node.lineno, ast.unparse(node)))
    return out


class FleetRoutingRule(Rule):
    id = "ROUTE-001"
    severity = CRITICAL
    title = (
        "fleet routing decisions only in replica.py + affinity.py"
    )
    rationale = (
        "DEVIATIONS §17: prefix-affinity placement is one policy "
        "with one precedence (phase > affinity > adapter residency "
        "> load), enforced where the pool admits requests. A digest-"
        "map read or an ad-hoc candidate ranking anywhere else "
        "forks the policy — two components can then route the same "
        "prompt to different replicas, which silently halves the "
        "fleet hit rate the digest map exists to protect, and a "
        "poke at the map's _by_digest/_by_replica mints stale "
        "routes the drop-on-death path can never retract."
    )

    def applies(self, src: SourceFile) -> bool:
        return _in_serving(src) and not any(
            _matches_file(src.rel, key) for key in _ROUTING_EXEMPT
        )

    def check(self, src: SourceFile) -> List[Finding]:
        return [
            self.finding(
                src,
                lineno,
                f"{what} — routing decisions belong to "
                "serving/replica.py + serving/affinity.py only; "
                "submit through the pool and observe through "
                "routing_stats()",
            )
            for lineno, what in routing_sites(src.tree)
        ]


# ---------------------------------------------------------------------------
# TIER-001: admission preemption only in scheduler.py + paged_kv.py


SCHEDULER_FILE = SERVING_PREFIX + "scheduler.py"
_PREEMPT_EXEMPT = (SCHEDULER_FILE, PAGED_KV_FILE)

# the admission-preemption API owned by serving/scheduler.py: the
# decision to evict a running request so a latency-tier arrival can
# admit. Distinct from the engine's memory-pressure preempt-and-swap
# (_preempt_slot — a page-pool survival move, not a policy): tier
# policy lives in the scheduler, and only the scheduler may trade one
# request's slot for another's admission.
_PREEMPT_CALLS = frozenset(
    {
        "_preempt_for_admission_locked",
        "preempt_for_admission",
    }
)


def preemption_sites(tree: ast.AST) -> List[Tuple[int, str]]:
    """(lineno, what) for every admission-preemption call (bare name
    or any attribute spelling, e.g. sched._preempt_for_admission_locked)."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Name) and f.id in _PREEMPT_CALLS:
            out.append((node.lineno, f"{f.id}(...)"))
        elif (
            isinstance(f, ast.Attribute) and f.attr in _PREEMPT_CALLS
        ):
            out.append((node.lineno, f"{ast.unparse(f)}(...)"))
    return out


class TierPreemptionRule(Rule):
    id = "TIER-001"
    severity = CRITICAL
    title = (
        "admission preemption only in scheduler.py + paged_kv.py"
    )
    rationale = (
        "DEVIATIONS §18: evicting a running request to admit a "
        "latency-tier arrival is a scheduler policy decision — it "
        "must snapshot the victim's resume ticket (journaled PRNG "
        "key + emitted tokens) BEFORE cancelling the slot, or the "
        "byte-parity resume guarantee breaks. The engine and pool "
        "never preempt for admission on their own: an engine-level "
        "eviction bypasses the journal, and a pool-level one forks "
        "tier policy across layers. The engine's memory-pressure "
        "preempt-and-swap and the page pool's reclaim remain the "
        "separate, legal survival paths."
    )

    def applies(self, src: SourceFile) -> bool:
        return _in_serving(src) and not any(
            _matches_file(src.rel, key) for key in _PREEMPT_EXEMPT
        )

    def check(self, src: SourceFile) -> List[Finding]:
        return [
            self.finding(
                src,
                lineno,
                f"{what} — admission preemption belongs to "
                "serving/scheduler.py (+ the page machinery in "
                "paged_kv.py) only; submit with a tier and let the "
                "scheduler's pump evict",
            )
            for lineno, what in preemption_sites(src.tree)
        ]


# ---------------------------------------------------------------------------
# PREFILL-001: the partial write frontier mutates only in engine
# admission/step and decode.py prefill programs


# engine.py functions allowed to write the frontier: construction and
# crash reset (mint/clear the vectors), the admission that installs
# it, the interleaved dispatcher that advances it, the release-path
# cleanup, and the fused chunk programs themselves. Everything else —
# scheduler, gateway, handoff, failover, tests-by-import — must treat
# it as read-only engine state: a frontier written anywhere else can
# desynchronize the host mirror from the device copy, and the
# byte-parity contract of chunked prefill rests on the mirror being
# dispatch-authoritative.
_FRONTIER_WRITERS = frozenset(
    {
        "__init__",
        "reset",
        "_device_state",
        "_admit",
        "_dispatch_interleaved",
        "_clear_prefill",
        "_run_pf",
        "_run_pf_paged",
    }
)


def _mentions_frontier(node: ast.AST) -> bool:
    """Whether an assignment-target subtree names the frontier in any
    spelling: a bare/attribute name containing "frontier"
    (self._frontier[slot] = ..., frontier = frontier.at[...]) or a
    "frontier" string key (d["frontier"] = ...). Reads and call NAMES
    (e.g. self._cow_frontier(...)) are not writes and never match."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and "frontier" in sub.id:
            return True
        if isinstance(sub, ast.Attribute) and "frontier" in sub.attr:
            return True
        if (
            isinstance(sub, ast.Constant)
            and isinstance(sub.value, str)
            and sub.value == "frontier"
        ):
            return True
    return False


def frontier_write_sites(
    tree: ast.AST,
) -> List[Tuple[int, str, Optional[str]]]:
    """(lineno, what, enclosing-function) for every statement that
    WRITES a frontier: plain/aug/annotated assignments whose target
    mentions it, and `frontier=` call keywords (d.update(frontier=…)
    mutates the device-state dict exactly like a subscript store)."""
    out = []
    for node, owner in walk_with_owner(tree):
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = (
                node.targets
                if isinstance(node, ast.Assign)
                else [node.target]
            )
            for t in targets:
                if _mentions_frontier(t):
                    out.append(
                        (node.lineno, f"{ast.unparse(t)} = ...", owner)
                    )
                    break
        elif isinstance(node, ast.Call):
            for kw in node.keywords:
                if kw.arg is not None and "frontier" in kw.arg:
                    out.append(
                        (node.lineno, f"{kw.arg}=... keyword", owner)
                    )
                    break
    return out


class PrefillFrontierRule(Rule):
    id = "PREFILL-001"
    severity = CRITICAL
    title = (
        "partial write frontier mutates only in engine "
        "admission/step and decode.py prefill programs"
    )
    rationale = (
        "DEVIATIONS §19: the frontier is the mid-prefill slot's ONE "
        "source of truth — the host mirror is dispatch-authoritative "
        "(the fetched device copy is never folded back, so an async "
        "harvest cannot regress it) and every byte-parity argument "
        "for interleaved chunked prefill assumes the only writers "
        "are the admission that installs it, the dispatcher that "
        "advances it chunk by chunk, the release paths that clear "
        "it, and the fused programs themselves. A write anywhere "
        "else (scheduler policy, gateway handlers, failover replay) "
        "can desynchronize mirror and device, corrupting resume "
        "tickets and the flip-to-decode re-key."
    )

    def applies(self, src: SourceFile) -> bool:
        # decode.py's chunked-prefill primitives are legal writers
        # wholesale; everything under serving/ is in scope, with
        # engine.py reduced to the writer allowlist below
        return _in_serving(src) and not _matches_file(
            src.rel, DECODE_FILE
        )

    def check(self, src: SourceFile) -> List[Finding]:
        in_engine = _matches_file(src.rel, ENGINE_FILE)
        out = []
        for lineno, what, owner in frontier_write_sites(src.tree):
            if in_engine and owner in _FRONTIER_WRITERS:
                continue
            out.append(
                self.finding(
                    src,
                    lineno,
                    f"{what} — the partial write frontier may only "
                    "mutate in engine admission/step "
                    "(_admit/_dispatch_interleaved/_clear_prefill) "
                    "and models/decode.py prefill programs; read it "
                    "through request_progress()/prefill_stats()",
                )
            )
        return out


# ---------------------------------------------------------------------------
# HBM-001: HBM<->host transfer primitives only in designated movers


# the raw transfer primitives: starting an async D2H copy on a device
# buffer, placing host bytes onto a device sharding, and the blocking
# fetch. Any spelling counts — a direct `arr.copy_to_host_async()`,
# the getattr("copy_to_host_async") duck-typed form, bare or
# attributed device_put/device_get.
_HBM_TRANSFER_CALLS = frozenset({"device_put", "device_get"})
_HBM_ASYNC_ATTR = "copy_to_host_async"

# functions allowed to move bytes across the PCIe boundary, per
# serving file. engine.py: the ONE async D2H starter plus the
# construction-time placement helpers ELASTIC-001 already pins;
# handoff.py: adoption places shipped KV onto the target sharding;
# kv_tier.py IS the tier-transfer module — its snapshot (D2H) and
# upload (H2D) helpers plus its single blocking fetch. Serving files
# not listed allow nothing.
_HBM_ALLOWED: Dict[str, FrozenSet[str]] = {
    ENGINE_FILE: frozenset(
        {"_start_host_copy", "_shard_bank", "_replicate"}
    ),
    HANDOFF_FILE: frozenset({"adopt_into_slot"}),
    KV_TIER_FILE: frozenset(
        {
            "snapshot_row",
            "snapshot_pages",
            "upload_row",
            "upload_pages",
            "_fetch",
        }
    ),
}


def hbm_transfer_sites(
    tree: ast.AST,
) -> List[Tuple[int, str, Optional[str]]]:
    """(lineno, what, enclosing-function-name) for every HBM<->host
    transfer primitive: device_put/device_get calls in any spelling,
    `.copy_to_host_async` attribute uses, and the duck-typed
    getattr(x, "copy_to_host_async", ...) form."""
    out = []
    for node, owner in walk_with_owner(tree):
        if isinstance(node, ast.Call):
            f = node.func
            if (
                isinstance(f, ast.Name)
                and f.id in _HBM_TRANSFER_CALLS
            ):
                out.append((node.lineno, f"{f.id}(...)", owner))
            elif (
                isinstance(f, ast.Attribute)
                and f.attr in _HBM_TRANSFER_CALLS
            ):
                out.append(
                    (node.lineno, f"{ast.unparse(f)}(...)", owner)
                )
            elif (
                isinstance(f, ast.Name)
                and f.id == "getattr"
                and len(node.args) >= 2
                and isinstance(node.args[1], ast.Constant)
                and node.args[1].value == _HBM_ASYNC_ATTR
            ):
                out.append(
                    (
                        node.lineno,
                        f'getattr(..., "{_HBM_ASYNC_ATTR}")',
                        owner,
                    )
                )
        elif (
            isinstance(node, ast.Attribute)
            and node.attr == _HBM_ASYNC_ATTR
        ):
            out.append((node.lineno, ast.unparse(node), owner))
    return out


class HbmTransferRule(Rule):
    id = "HBM-001"
    severity = CRITICAL
    title = (
        "HBM<->host transfer primitives only in designated movers"
    )
    rationale = (
        "DEVIATIONS §20: with a host-DRAM KV tier in the stack, KV "
        "bytes cross PCIe in exactly three places — the engine's "
        "async dispatch fetch, handoff adoption, and the tier's "
        "snapshot/upload helpers in serving/kv_tier.py. A stray "
        "copy_to_host_async or device_put on a KV-shaped array "
        "anywhere else is an unaccounted PCIe transfer: it serializes "
        "against the dispatch pipeline, dodges the tier's byte "
        "budget, and hides from the demotion/promotion counters the "
        "bench contracts assert on."
    )

    def applies(self, src: SourceFile) -> bool:
        return _in_serving(src)

    def check(self, src: SourceFile) -> List[Finding]:
        allowed = _file_config(src.rel, _HBM_ALLOWED) or frozenset()
        return [
            self.finding(
                src,
                lineno,
                f"{what} in {owner or '<module>'}() — HBM<->host "
                f"transfers allowed only in "
                f"{sorted(allowed) or 'nothing in this file'}; move "
                "KV through serving/kv_tier.py or the engine's "
                "designated fetch/placement helpers",
            )
            for lineno, what, owner in hbm_transfer_sites(src.tree)
            if owner not in allowed
        ]


# ---------------------------------------------------------------------------
# INTEG-001: KV integrity checksum discipline


HEALTH_FILE = SERVING_PREFIX + "health.py"

# the checksum primitives: the sentinel's own compute/verify helpers
# plus raw blake2b in any spelling (hashlib.blake2b attribute or a
# bare imported name)
_INTEG_CALLS = frozenset(
    {"kv_checksum", "verify_checksum", "blake2b"}
)

# functions allowed to compute or verify digests, per serving file.
# health.py is the checksum module itself (excluded wholesale below);
# affinity.py chains routing digests (identity, not integrity — but
# the same blake2b primitive, so it must be pinned here or the rule
# would flag it); kv_tier.py stamps at _finalize and verifies at its
# one ingress gate; handoff.py stamps at export, verifies at the
# coordinator ingress (on_prefill_done, before any target enqueues
# the package) and again at direct adoption for out-of-band callers.
# Serving files not listed allow nothing.
_INTEG_ALLOWED: Dict[str, FrozenSet[str]] = {
    AFFINITY_FILE: frozenset({"_block_digest"}),
    KV_TIER_FILE: frozenset({"_finalize", "_verify_locked"}),
    HANDOFF_FILE: frozenset(
        {"export_run", "adopt_into_slot", "on_prefill_done"}
    ),
}


def integrity_checksum_sites(
    tree: ast.AST,
) -> List[Tuple[int, str, Optional[str]]]:
    """(lineno, what, enclosing-function-name) for every checksum
    primitive call: kv_checksum/verify_checksum in any spelling, and
    blake2b both bare and as hashlib.blake2b."""
    out = []
    for node, owner in walk_with_owner(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Name) and f.id in _INTEG_CALLS:
            out.append((node.lineno, f"{f.id}(...)", owner))
        elif (
            isinstance(f, ast.Attribute) and f.attr in _INTEG_CALLS
        ):
            out.append(
                (node.lineno, f"{ast.unparse(f)}(...)", owner)
            )
    return out


class IntegrityChecksumRule(Rule):
    id = "INTEG-001"
    severity = CRITICAL
    title = (
        "KV checksum compute/verify only at designated "
        "egress/ingress sites"
    )
    rationale = (
        "DEVIATIONS §21: KV payload digests are stamped at exactly "
        "two egress points (tier finalize, handoff export) and "
        "verified at the matching ingress gates — that pairing is "
        "what makes a mismatch attributable to in-transit "
        "corruption. A checksum computed anywhere else either "
        "re-hashes device buffers mid-flight (digesting garbage the "
        "D2H copy hasn't landed), double-counts the integrity "
        "telemetry the bench contract asserts on, or silently "
        "shadows the quarantine path so corrupted bytes reach "
        "decode."
    )

    def applies(self, src: SourceFile) -> bool:
        # the checksum module itself is the one place allowed to
        # spell the primitives freely
        return _in_serving(src) and not _matches_file(
            src.rel, HEALTH_FILE
        )

    def check(self, src: SourceFile) -> List[Finding]:
        allowed = _file_config(src.rel, _INTEG_ALLOWED) or frozenset()
        return [
            self.finding(
                src,
                lineno,
                f"{what} in {owner or '<module>'}() — checksum "
                f"compute/verify allowed only in "
                f"{sorted(allowed) or 'nothing in this file'}; stamp "
                "at tier finalize / handoff export and verify at the "
                "matching ingress via serving/health.py helpers",
            )
            for lineno, what, owner in integrity_checksum_sites(
                src.tree
            )
            if owner not in allowed
        ]


# ---------------------------------------------------------------------------
# QUANT-001: weight-quantization call-site discipline


# the quantization primitives (ops/quantization.py): the per-block
# int8 pair plus the stochastic-rounding variant, in any spelling
# (bare imported name or module attribute)
_QUANT_CALLS = frozenset(
    {"quantize_int8", "dequantize_int8", "stochastic_round_int8"}
)

# functions allowed to quantize/dequantize, per file. The engine's
# _quantize_params is THE designated install site: weights quantize
# once, at param install (construction / committed refresh), never
# per-step. models/decode.py is in scope but allows nothing — its
# forward paths consume QuantizedWeight via matmul_any's fused
# dequant and must never re-materialize dense weights. Serving files
# not listed allow nothing.
_QUANT_ALLOWED: Dict[str, FrozenSet[str]] = {
    ENGINE_FILE: frozenset({"_quantize_params"}),
}


def weight_quant_sites(
    tree: ast.AST,
) -> List[Tuple[int, str, Optional[str]]]:
    """(lineno, what, enclosing-function-name) for every quantization
    primitive call: quantize_int8/dequantize_int8/
    stochastic_round_int8, bare or as a module attribute."""
    out = []
    for node, owner in walk_with_owner(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Name) and f.id in _QUANT_CALLS:
            out.append((node.lineno, f"{f.id}(...)", owner))
        elif (
            isinstance(f, ast.Attribute) and f.attr in _QUANT_CALLS
        ):
            out.append(
                (node.lineno, f"{ast.unparse(f)}(...)", owner)
            )
    return out


class WeightQuantSiteRule(Rule):
    id = "QUANT-001"
    severity = CRITICAL
    title = (
        "weight quantize/dequantize only at the designated "
        "install site"
    )
    rationale = (
        "DEVIATIONS §22: served weights quantize exactly once, at "
        "param install (engine construction or a committed "
        "version-fenced refresh) — the whole point is that decode "
        "then streams int8 bytes from HBM. A quantize call anywhere "
        "else in the serving path either re-quantizes per step "
        "(burning the bandwidth the feature exists to save, and "
        "double-rounding the weights), or silently diverges from "
        "the installed banks so the kernel-vs-reference parity and "
        "byte-accounting contracts test a tree that is not the one "
        "serving. A dequantize call in the forward path "
        "re-materializes the dense weights — the fused matmul_any "
        "path is the only sanctioned consumer."
    )

    def applies(self, src: SourceFile) -> bool:
        return _in_serving(src) or _matches_file(
            src.rel, DECODE_FILE
        )

    def check(self, src: SourceFile) -> List[Finding]:
        allowed = _file_config(src.rel, _QUANT_ALLOWED) or frozenset()
        return [
            self.finding(
                src,
                lineno,
                f"{what} in {owner or '<module>'}() — weight "
                f"quantization allowed only in "
                f"{sorted(allowed) or 'nothing in this file'}; "
                "quantize at the engine's _quantize_params install "
                "site and consume via ops.quantization.matmul_any",
            )
            for lineno, what, owner in weight_quant_sites(src.tree)
            if owner not in allowed
        ]


# ---------------------------------------------------------------------------
# registry


REGISTRY: List[Rule] = [
    RlImportRule(),
    HostCopyRule(),
    DeviceAllocRule(),
    RawMeshRule(),
    LockDisciplineRule(),
    ClockDisciplineRule(),
    JitSelfCaptureRule(),
    EagerJnpImportRule(),
    ProgramCacheKeyRule(),
    BroadExceptRule(),
    KernelHygieneRule(),
    HandoffAdoptionRule(),
    ElasticReshardRule(),
    AdapterBankRule(),
    FleetRoutingRule(),
    TierPreemptionRule(),
    PrefillFrontierRule(),
    HbmTransferRule(),
    IntegrityChecksumRule(),
    WeightQuantSiteRule(),
]


def get_rules(ids: Optional[List[str]] = None) -> List[Rule]:
    if ids is None:
        return list(REGISTRY)
    by_id = {r.id: r for r in REGISTRY}
    missing = [i for i in ids if i not in by_id]
    if missing:
        raise KeyError(
            f"unknown rule id(s): {missing}; known: {sorted(by_id)}"
        )
    return [by_id[i] for i in ids]
