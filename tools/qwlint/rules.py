"""The five qwlint rules. Each rule is an object with `id`, `title`, and
`check(ctx: FileContext)`; cross-file rules may also define
`finalize(shared) -> list[Finding]` which the runner calls once after
every file has been checked."""

from __future__ import annotations

import ast
import re
from typing import Optional

from .core import FileContext, Finding, dotted_name, last_segment

# --- QW001 hidden-host-readback ---------------------------------------------

_HOT_PATH_MODULES = (
    "quickwit_tpu/ops/",
    # compaction merges re-run the impact quantizer over every surviving
    # posting; a hidden readback or per-merge jit there multiplies by the
    # merge fan-in, not the query rate
    "quickwit_tpu/compaction/",
    "quickwit_tpu/search/executor.py",
    "quickwit_tpu/search/leaf.py",
    "quickwit_tpu/search/collector.py",
    "quickwit_tpu/search/plan.py",
    # hierarchical cache tiers sit on the per-split hot path: a mask/agg
    # consult or fill must never smuggle in a device readback of its own
    "quickwit_tpu/search/mask_cache.py",
    "quickwit_tpu/search/agg_cache.py",
    "quickwit_tpu/search/tenant_cache.py",
    # write-time impact quantization: numpy-only by contract (its scores
    # must mirror ops/bm25.py bit-for-bit, and merge re-runs it per field)
    "quickwit_tpu/index/impact.py",
    # the audited host-decode seam: conversions are ALLOWED here (each is
    # individually suppressed with its contract), nowhere else
    "quickwit_tpu/search/hostdecode.py",
)

_READBACK_BUILTINS = {"float", "int", "bool"}
_READBACK_METHODS = {"item", "block_until_ready"}
_READBACK_DOTTED = {"np.asarray", "numpy.asarray", "jax.device_get"}


def _is_constantish(node: ast.AST) -> bool:
    """Literals and signed literals: `float("-inf")`, `int(-1)` are host
    constants, not readbacks."""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.UnaryOp) and isinstance(node.operand,
                                                    ast.Constant):
        return True
    if isinstance(node, (ast.List, ast.Tuple)):
        return all(_is_constantish(e) for e in node.elts)
    return False


class HiddenHostReadback:
    id = "QW001"
    title = "hidden-host-readback"

    def check(self, ctx: FileContext) -> None:
        if not ctx.in_package_scope(_HOT_PATH_MODULES):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if not getattr(node, "_qw_funcs", ()):
                continue  # module level runs at import time, not per query
            func = node.func
            if (isinstance(func, ast.Name)
                    and func.id in _READBACK_BUILTINS
                    and len(node.args) == 1 and not node.keywords
                    and not _is_constantish(node.args[0])):
                ctx.add(self.id, node,
                        f"{func.id}() on a possibly-device value forces a "
                        "device→host sync (ROADMAP item 1: readback_wait_ms "
                        "dominates the hot path); compute on device, move "
                        "it behind the packed readback seam, or suppress "
                        "with a justification if the value is already host "
                        "numpy")
                continue
            if (isinstance(func, ast.Attribute)
                    and func.attr in _READBACK_METHODS
                    and not node.args and not node.keywords):
                ctx.add(self.id, node,
                        f".{func.attr}() blocks on device completion and "
                        "copies to host; hot-path code must batch "
                        "readbacks through the packed seam "
                        "(search/executor.py::readback_plan_result)")
                continue
            name = dotted_name(func)
            if name in _READBACK_DOTTED and node.args \
                    and not _is_constantish(node.args[0]):
                ctx.add(self.id, node,
                        f"{name}() materializes its argument on host — a "
                        "silent transfer when the argument is a device "
                        "array; keep hot-path data device-resident")


# --- QW002 recompilation-hazard ---------------------------------------------

_CACHE_NAME_RE = re.compile(r"_CACHE")


def _is_jit_call(node: ast.Call) -> bool:
    name = dotted_name(node.func)
    if name in ("jax.jit", "jit"):
        return True
    # functools.partial(jax.jit, ...) builds a jit factory
    if last_segment(node.func) == "partial" and node.args:
        return dotted_name(node.args[0]) in ("jax.jit", "jit")
    return False


class RecompilationHazard:
    id = "QW002"
    title = "recompilation-hazard"

    def check(self, ctx: FileContext) -> None:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call) or not _is_jit_call(node):
                continue
            self._check_static_args(ctx, node)
            if not getattr(node, "_qw_funcs", ()):
                continue  # module-level jit compiles once per process
            parent = getattr(node, "_qw_parent", None)
            if isinstance(parent, ast.Call) and parent.func is node:
                ctx.add(self.id, node,
                        "jax.jit(...)(...) creates and invokes a fresh "
                        "compiled callable per call — every query "
                        "recompiles; hoist the jitted callable to module "
                        "level or memoize it in a plan-keyed cache "
                        "(executor.py _JIT_CACHE pattern)")
                continue
            if self._reaches_cache(ctx, node):
                continue
            ctx.add(self.id, node,
                    "jax.jit created inside a function without a "
                    "*_CACHE store or builder return — if this runs per "
                    "query, each call pays a full XLA compile; memoize "
                    "keyed by plan structure, never by request values")

    @staticmethod
    def _check_static_args(ctx: FileContext, node: ast.Call) -> None:
        for kw in node.keywords:
            if kw.arg not in ("static_argnums", "static_argnames"):
                continue
            if not _static_spec_is_literal(kw.value):
                ctx.add(RecompilationHazard.id, node,
                        f"{kw.arg} computed at runtime: request-derived "
                        "values in static positions key the jit cache — "
                        "every distinct per-query value triggers a "
                        "recompile; statics must be plan-structure "
                        "constants")

    @staticmethod
    def _reaches_cache(ctx: FileContext, node: ast.Call) -> bool:
        """The builder idioms that are NOT hazards: the jit object is
        returned to a caller that caches it, or the enclosing function
        itself touches a *_CACHE name (memoizing getter)."""
        stmt = ctx.statement_of(node)
        if isinstance(stmt, ast.Return):
            return True
        for fn in ctx.enclosing_defs(node):
            for inner in ast.walk(fn):
                if isinstance(inner, ast.Name) \
                        and _CACHE_NAME_RE.search(inner.id):
                    return True
        return False


def _static_spec_is_literal(node: ast.AST) -> bool:
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, (ast.Tuple, ast.List)):
        return all(isinstance(e, ast.Constant) for e in node.elts)
    return False


# --- QW003 ambient-context-propagation --------------------------------------

_CTX_WRAPPERS = {"run_with_context", "bind_deadline", "bind_tenant",
                 "bind_profile"}


def _wrapped_names(tree: ast.AST) -> set[str]:
    """Names assigned from a wrapper call (`run = run_with_context(f)`) are
    wrapped callables too — the spawn site may be lines away."""
    names: set[str] = set()
    for node in ast.walk(tree):
        value = getattr(node, "value", None)
        if not (isinstance(node, (ast.Assign, ast.AnnAssign))
                and isinstance(value, ast.Call)
                and last_segment(value.func) in _CTX_WRAPPERS):
            continue
        targets = node.targets if isinstance(node, ast.Assign) \
            else [node.target]
        names.update(t.id for t in targets if isinstance(t, ast.Name))
    return names


_POOL_RECEIVER_RE = re.compile(r"pool|executor", re.IGNORECASE)


def _is_pool_receiver(node: ast.AST) -> bool:
    """`.submit` is only a thread hop on pools/executors — a work-queue
    `.submit(task)` (compaction supervisor) takes data, not a callable."""
    if isinstance(node, ast.Call):
        node = node.func
    return bool(_POOL_RECEIVER_RE.search(last_segment(node) or ""))


def _is_wrapped_callable(node: ast.AST, wrapped: set[str]) -> bool:
    if isinstance(node, ast.Call) \
            and last_segment(node.func) in _CTX_WRAPPERS:
        return True
    return isinstance(node, ast.Name) and node.id in wrapped


class AmbientContextPropagation:
    id = "QW003"
    title = "ambient-context-propagation"

    def check(self, ctx: FileContext) -> None:
        wrapped = _wrapped_names(ctx.tree)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            # "thread" covers the seam factory (common.sync.thread): the
            # contextvars hop is identical whichever constructor spawns it
            if last_segment(node.func) in ("Thread", "thread"):
                target = next((kw.value for kw in node.keywords
                               if kw.arg == "target"), None)
                if target is not None \
                        and not _is_wrapped_callable(target, wrapped):
                    ctx.add(self.id, node,
                            "threading.Thread(target=...) with a bare "
                            "callable: the new thread starts with EMPTY "
                            "contextvars, silently dropping the caller's "
                            "deadline/tenant/profile bindings — wrap the "
                            "target with common.ctx.run_with_context (or "
                            "suppress if the thread never serves a query)")
            elif (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "submit" and node.args
                    and _is_pool_receiver(node.func.value)):
                if not _is_wrapped_callable(node.args[0], wrapped):
                    ctx.add(self.id, node,
                            "executor.submit(fn, ...) with a bare "
                            "callable: pool worker threads do not inherit "
                            "contextvars — deadline/tenant/profile vanish "
                            "across the hop; wrap fn with "
                            "common.ctx.run_with_context")


# --- QW004 swallowed-control-flow -------------------------------------------

_QUERY_PATH_MODULES = (
    "quickwit_tpu/search/",
    "quickwit_tpu/serve/",
    "quickwit_tpu/storage/",
    "quickwit_tpu/parallel/",
    "quickwit_tpu/offload/",
)

_TYPED_CONTROL_FLOW = {"OverloadShed", "TenantRateLimited",
                       "DeadlineExceeded", "InjectedFault"}
# calling one of these inside the handler counts as classifying the
# exception rather than swallowing it
_CLASSIFIER_HELPERS = {"is_deadline_error", "classify_exception"}

_BROAD_NAMES = {"Exception", "BaseException"}


def _handler_type_names(handler: ast.ExceptHandler) -> set[str]:
    if handler.type is None:
        return set()
    nodes = handler.type.elts if isinstance(handler.type, ast.Tuple) \
        else [handler.type]
    return {last_segment(n) for n in nodes}


def _references_control_flow(handler: ast.ExceptHandler) -> bool:
    wanted = _TYPED_CONTROL_FLOW | _CLASSIFIER_HELPERS
    for node in ast.walk(handler):
        if isinstance(node, ast.Raise) and node.exc is None:
            return True  # bare re-raise
        if isinstance(node, ast.Name) and node.id in wanted:
            return True
        if isinstance(node, ast.Attribute) and node.attr in wanted:
            return True
    return False


class SwallowedControlFlow:
    id = "QW004"
    title = "swallowed-control-flow"

    def check(self, ctx: FileContext) -> None:
        if not ctx.in_package_scope(_QUERY_PATH_MODULES):
            return
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Try):
                continue
            shielded = False
            for handler in node.handlers:
                names = _handler_type_names(handler)
                if names & _TYPED_CONTROL_FLOW:
                    shielded = True  # typed clause runs before the broad one
                    continue
                is_broad = handler.type is None or names & _BROAD_NAMES
                if not is_broad or shielded:
                    continue
                if _references_control_flow(handler):
                    continue
                ctx.add(self.id, handler,
                        "broad except on the query path swallows typed "
                        "control-flow exceptions (DeadlineExceeded, "
                        "OverloadShed, TenantRateLimited, InjectedFault) "
                        "into generic failures — re-raise them first "
                        "(`except (OverloadShed, TenantRateLimited): "
                        "raise`) or classify inside the handler")


# --- QW005 metrics-hygiene --------------------------------------------------

_METRIC_FACTORIES = {"counter", "gauge", "histogram"}
_METRIC_OBSERVERS = {"inc", "observe", "set", "add"}
_METRIC_RECEIVER_RE = re.compile(r"^_?[A-Z][A-Z0-9_]*$")
_HIGH_CARDINALITY_LABELS = {"query", "query_str", "doc_id", "split_id",
                            "trace_id", "span_id", "request_id", "path",
                            "uri", "url", "user", "opaque_id"}


class MetricsHygiene:
    id = "QW005"
    title = "metrics-hygiene"

    def check(self, ctx: FileContext) -> None:
        registrations = ctx.shared.setdefault("qw005_registrations", [])
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if (isinstance(func, ast.Attribute)
                    and func.attr in _METRIC_FACTORIES
                    and last_segment(func.value) == "METRICS"
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                name = node.args[0].value
                if not name.startswith("qw_"):
                    ctx.add(self.id, node,
                            f"metric {name!r} is not qw_-prefixed — every "
                            "exported series must carry the namespace "
                            "prefix (reference: quickwit-metrics "
                            "new_counter! conventions)")
                registrations.append({
                    "name": name, "path": ctx.relpath,
                    "function": getattr(node, "_qw_qual", "<module>"),
                    "line": node.lineno, "col": node.col_offset,
                    "suppressed": ctx.suppressed(self.id, node)})
                continue
            if (isinstance(func, ast.Attribute)
                    and func.attr in _METRIC_OBSERVERS
                    and isinstance(func.value, ast.Name)
                    and _METRIC_RECEIVER_RE.match(func.value.id)):
                for kw in node.keywords:
                    if kw.arg in _HIGH_CARDINALITY_LABELS:
                        ctx.add(self.id, node,
                                f"label {kw.arg!r} is unbounded per-query "
                                "cardinality — each distinct value mints "
                                "a new series; aggregate, hash-bucket, or "
                                "drop the label (tenancy/registry.py "
                                "shows the bounded pattern)")
                    elif isinstance(kw.value, ast.JoinedStr):
                        ctx.add(self.id, node,
                                f"f-string value for label {kw.arg!r}: "
                                "interpolated label values are an "
                                "unbounded-cardinality trap; use a small "
                                "closed vocabulary")

    def finalize(self, shared: dict) -> list[Finding]:
        by_name: dict[str, list[dict]] = {}
        for reg in shared.get("qw005_registrations", []):
            by_name.setdefault(reg["name"], []).append(reg)
        findings = []
        for name, regs in sorted(by_name.items()):
            if len(regs) < 2:
                continue
            regs.sort(key=lambda r: (r["path"], r["line"]))
            first = regs[0]
            for reg in regs[1:]:
                if reg["suppressed"]:
                    continue
                findings.append(Finding(
                    rule=self.id, path=reg["path"], line=reg["line"],
                    col=reg["col"], function=reg["function"],
                    message=(f"metric {name!r} already registered at "
                             f"{first['path']}:{first['line']} — duplicate "
                             "registration either aliases state across "
                             "modules or raises TypeError on a type "
                             "mismatch at import time")))
        return findings


# --- QW006 ambient-time-and-randomness ---------------------------------------

# Modules the DST harness simulates: everything here must read time and
# randomness through quickwit_tpu/common/clock.py, or a seeded run is no
# longer deterministic (and scenario hours cost wall-clock hours). The
# clock seam itself (common/clock.py) is intentionally NOT scoped — it is
# the one place ambient time is allowed. External-source adapters
# (kinesis/aws_json/fake_sqs) and the sql metastore stay unscoped until
# they grow simulation coverage.
_SIM_SCOPED_MODULES = (
    "quickwit_tpu/common/actors.py",
    "quickwit_tpu/common/deadline.py",
    "quickwit_tpu/common/faults.py",
    "quickwit_tpu/common/tower.py",
    "quickwit_tpu/cluster/",
    "quickwit_tpu/control_plane/",
    "quickwit_tpu/dst/",
    "quickwit_tpu/indexing/cooperative.py",
    "quickwit_tpu/indexing/merge.py",
    "quickwit_tpu/indexing/pipeline.py",
    "quickwit_tpu/indexing/sources.py",
    "quickwit_tpu/ingest/ingester.py",
    "quickwit_tpu/ingest/router.py",
    "quickwit_tpu/ingest/wal.py",
    "quickwit_tpu/metastore/file_backed.py",
    "quickwit_tpu/models/index_metadata.py",
    "quickwit_tpu/models/split_metadata.py",
    "quickwit_tpu/observability/flight.py",
    "quickwit_tpu/observability/profiler.py",
    "quickwit_tpu/observability/slo.py",
    "quickwit_tpu/offload/",
    "quickwit_tpu/tenancy/overload.py",
)

_TIME_ATTRS = {"time", "monotonic", "sleep", "time_ns", "monotonic_ns",
               "perf_counter", "perf_counter_ns"}
# module-level random.* draws share one unseedable global stream;
# random.Random(seed) / random.SystemRandom() construction is fine
_RANDOM_ATTRS = {"random", "randint", "randrange", "randbytes", "choice",
                 "choices", "shuffle", "sample", "uniform", "gauss",
                 "getrandbits", "normalvariate", "expovariate",
                 "triangular", "betavariate", "paretovariate",
                 "vonmisesvariate", "weibullvariate", "lognormvariate"}
_DATETIME_DOTTED = {"datetime.now", "datetime.utcnow",
                    "datetime.datetime.now", "datetime.datetime.utcnow",
                    "date.today", "datetime.date.today"}


class AmbientTimeAndRandomness:
    id = "QW006"
    title = "ambient-time-and-randomness"

    def _message(self, what: str) -> str:
        return (f"direct {what} in a simulation-scoped module: the DST "
                "harness cannot virtualize it, so seeded runs stop being "
                "deterministic and scenario hours cost wall-clock hours — "
                "route through quickwit_tpu.common.clock (get_clock(), "
                "monotonic()/wall_time()/sleep(), get_rng())")

    def check(self, ctx: FileContext) -> None:
        if not ctx.in_package_scope(_SIM_SCOPED_MODULES):
            return
        if ctx.relpath.endswith("common/clock.py"):
            return  # the seam itself: ambient time is its job
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ImportFrom) and node.level == 0:
                if node.module == "time":
                    bad = sorted(a.name for a in node.names
                                 if a.name in _TIME_ATTRS)
                    if bad:
                        ctx.add(self.id, node, self._message(
                            f"`from time import {', '.join(bad)}`"))
                elif node.module == "random":
                    bad = sorted(a.name for a in node.names
                                 if a.name in _RANDOM_ATTRS)
                    if bad:
                        ctx.add(self.id, node, self._message(
                            f"`from random import {', '.join(bad)}`"))
                continue
            if not isinstance(node, ast.Attribute):
                continue
            dotted = dotted_name(node)
            if dotted in _DATETIME_DOTTED:
                ctx.add(self.id, node, self._message(f"{dotted}()"))
            elif (isinstance(node.value, ast.Name)
                    and node.value.id == "time"
                    and node.attr in _TIME_ATTRS):
                # a bare reference (e.g. `clock=time.monotonic` default)
                # is as ambient as a call
                ctx.add(self.id, node, self._message(f"time.{node.attr}"))
            elif (isinstance(node.value, ast.Name)
                    and node.value.id == "random"
                    and node.attr in _RANDOM_ATTRS):
                ctx.add(self.id, node,
                        self._message(f"random.{node.attr}"))


# --- QW007 lock-order-hazard -------------------------------------------------

# A name is treated as a lock when its last dotted segment is `lock`/`mutex`
# or ends with `_lock`/`_LOCK` — matches `_MESH_DISPATCH_LOCK`, the batcher/
# budget/cache `self._lock`s and `shard.persist_lock`, but not `deadlock`
# or condition variables (which wrap a lock and are named `_cv`/`_cond`).
_LOCK_NAME_RE = re.compile(r"(?:^|_)(?:lock|mutex)$", re.IGNORECASE)

# Device syncs that must not run while a lock is held: every waiter on the
# lock stalls for a device round-trip it never asked for. Reuses QW001's
# readback sets; `jax.block_until_ready(x)` is the call-form spelling.
_QW007_READBACK_DOTTED = _READBACK_DOTTED | {"jax.block_until_ready"}

_QW007_SHARED = "qw007_edges"
# every edge, suppressed included: a suppression waives the CYCLE report,
# not the edge's existence — tools/qwrace's lock-graph bridge compares the
# runtime witness graph against this full static graph
_QW007_ALL_SHARED = "qw007_all_edges"


class LockOrder:
    """Cross-file lock-acquisition-order analysis.

    Collects an acquisition graph: an edge A → B means some function
    acquires B (via `with B:` or `B.acquire()`) while already holding A.
    After every file is checked, `finalize` reports each edge that sits on
    a cycle of two or more distinct locks — two threads taking the same
    pair in opposite orders is a deadlock waiting for scheduler timing.
    Self-edges are skipped: re-entering the *name* usually means two
    instances (per-shard `persist_lock`) or an RLock, not a self-deadlock.

    Also flags device readbacks executed while any lock is held: the
    readback's latency becomes every waiter's latency.
    """

    id = "QW007"
    title = "lock-order-hazard"

    # -- lock identity -----------------------------------------------------
    def _lock_id(self, ctx: FileContext, expr: ast.AST) -> Optional[str]:
        name = dotted_name(expr)
        if not name or not _LOCK_NAME_RE.search(name.rsplit(".", 1)[-1]):
            return None
        parts = name.split(".")
        if parts[0] in ("self", "cls"):
            # rewrite `self._lock` to `ClassName._lock` so every method of
            # the class contributes to one node in the graph
            qual = getattr(expr, "_qw_qual", "<module>")
            funcs = getattr(expr, "_qw_funcs", ())
            segments = [] if qual == "<module>" else qual.split(".")
            cls = ".".join(segments[:len(segments) - len(funcs)])
            parts[0] = cls or parts[0]
        return ".".join(parts)

    # -- recording ---------------------------------------------------------
    def _record_edge(self, ctx: FileContext, held: str, acquired: str,
                     node: ast.AST) -> None:
        if held == acquired:
            return
        site = {"path": ctx.relpath,
                "line": getattr(node, "lineno", 0),
                "col": getattr(node, "col_offset", 0),
                "function": getattr(node, "_qw_qual", "<module>")}
        ctx.shared.setdefault(_QW007_ALL_SHARED, {}) \
                  .setdefault((held, acquired), []).append(site)
        if ctx.suppressed(self.id, node):
            return
        ctx.shared.setdefault(_QW007_SHARED, {}) \
                  .setdefault((held, acquired), []).append(site)

    def _scan_readbacks(self, ctx: FileContext, exprs, held) -> None:
        if not held:
            return
        stack = list(exprs)
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
                continue  # runs later, not under this lock
            stack.extend(ast.iter_child_nodes(node))
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            hit = None
            if (isinstance(func, ast.Attribute)
                    and func.attr in _READBACK_METHODS
                    and not node.args and not node.keywords):
                hit = f".{func.attr}()"
            else:
                name = dotted_name(func)
                if name in _QW007_READBACK_DOTTED and node.args:
                    hit = f"{name}()"
            if hit:
                locks = ", ".join(lock for lock, _ in held)
                ctx.add(self.id, node,
                        f"{hit} forces a device→host sync while holding "
                        f"{locks}: every thread waiting on the lock stalls "
                        "for the device round-trip; move the readback "
                        "outside the critical section or suppress with the "
                        "ordering argument that makes holding it necessary")

    # -- ordered traversal -------------------------------------------------
    def _visit_block(self, ctx: FileContext, stmts, held) -> None:
        held = list(held)
        for stmt in stmts:
            held = self._visit_stmt(ctx, stmt, held)

    def _visit_stmt(self, ctx: FileContext, stmt: ast.stmt, held):
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
            self._visit_block(ctx, stmt.body, [])  # runs with no locks held
            return held
        if isinstance(stmt, ast.ClassDef):
            self._visit_block(ctx, stmt.body, [])
            return held
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            self._scan_readbacks(ctx, [i.context_expr for i in stmt.items],
                                 held)
            inner = list(held)
            for item in stmt.items:
                lock = self._lock_id(ctx, item.context_expr)
                if lock is None:
                    continue
                for outer, _ in inner:
                    self._record_edge(ctx, outer, lock, item.context_expr)
                inner.append((lock, item.context_expr))
            self._visit_block(ctx, stmt.body, inner)
            return held
        if isinstance(stmt, (ast.If, ast.While)):
            self._scan_readbacks(ctx, [stmt.test], held)
            self._visit_block(ctx, stmt.body, held)
            self._visit_block(ctx, stmt.orelse, held)
            return held
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            self._scan_readbacks(ctx, [stmt.iter], held)
            self._visit_block(ctx, stmt.body, held)
            self._visit_block(ctx, stmt.orelse, held)
            return held
        if isinstance(stmt, (ast.Try, getattr(ast, "TryStar", ast.Try))):
            self._visit_block(ctx, stmt.body, held)
            for handler in stmt.handlers:
                self._visit_block(ctx, handler.body, held)
            self._visit_block(ctx, stmt.orelse, held)
            self._visit_block(ctx, stmt.finalbody, held)
            return held
        # simple statement: explicit acquire()/release() bookkeeping, then
        # readback scan under whatever is held
        call = stmt.value if isinstance(stmt, ast.Expr) \
            and isinstance(stmt.value, ast.Call) else None
        if call is not None and isinstance(call.func, ast.Attribute):
            lock = self._lock_id(ctx, call.func.value)
            if lock is not None and call.func.attr == "acquire":
                for outer, _ in held:
                    self._record_edge(ctx, outer, lock, call)
                return held + [(lock, call)]
            if lock is not None and call.func.attr == "release":
                return [(name, site) for name, site in held
                        if name != lock]
        self._scan_readbacks(ctx, [stmt], held)
        return held

    def check(self, ctx: FileContext) -> None:
        self._visit_block(ctx, ctx.tree.body, [])

    # -- cross-file cycle report -------------------------------------------
    def finalize(self, shared: dict) -> list[Finding]:
        edges = shared.get(_QW007_SHARED, {})
        adjacency: dict[str, set] = {}
        for src, dst in edges:
            adjacency.setdefault(src, set()).add(dst)
        findings: list[Finding] = []
        for (src, dst), sites in sorted(edges.items()):
            path = self._shortest_path(adjacency, dst, src)
            if path is None:
                continue  # edge not on any cycle
            cycle = " → ".join([src] + path)
            for site in sites:
                findings.append(Finding(
                    rule=self.id, path=site["path"], line=site["line"],
                    col=site["col"], function=site["function"],
                    message=f"acquires {dst} while holding {src}, but "
                            f"elsewhere the order is reversed (cycle: "
                            f"{cycle}); two threads taking these locks in "
                            "opposite orders deadlock — pick one global "
                            "order and restructure the losing site"))
        return findings

    @staticmethod
    def _shortest_path(adjacency: dict, start: str,
                       goal: str) -> Optional[list]:
        """BFS path start → goal through the acquisition graph, or None."""
        frontier = [[start]]
        seen = {start}
        while frontier:
            next_frontier = []
            for path in frontier:
                if path[-1] == goal:
                    return path
                for succ in sorted(adjacency.get(path[-1], ())):
                    if succ not in seen:
                        seen.add(succ)
                        next_frontier.append(path + [succ])
            frontier = next_frontier
        return None


# --- QW008 raw-threading-construction ----------------------------------------

# constructors the sync seam wraps; Timer/Barrier are unused in the tree
# and would be findings too if they appeared
_QW008_CTORS = {"Lock", "RLock", "Condition", "Event", "Semaphore",
                "BoundedSemaphore", "Thread"}


class RawThreadingConstruction:
    """Raw `threading.{Lock,RLock,Condition,Event,Semaphore,Thread}`
    construction outside `common/sync.py`.

    The sync seam is how `tools/qwrace` gates every thread under one
    seeded scheduler and records happens-before edges: a raw primitive is
    invisible to race detection (its release→acquire edges are missing,
    so accesses it actually protects report as races) and — worse — a raw
    lock held across an instrumented preemption point can park its holder
    while another thread blocks on the real lock, hanging the gated run.
    Construct through `quickwit_tpu.common.sync` (`lock()/rlock()/
    condition()/event()/semaphore()/thread()`), or suppress with the
    argument that makes the site safe (leaf critical section containing
    no seam operations, process-lifetime infrastructure thread, ...).
    """

    id = "QW008"
    title = "raw-threading-construction"

    def _message(self, what: str) -> str:
        return (f"raw {what} outside common/sync.py: invisible to the "
                "qwrace scheduler and happens-before detection — "
                "construct via quickwit_tpu.common.sync "
                "(lock()/rlock()/condition()/event()/semaphore()/"
                "thread()), or suppress with the argument that makes the "
                "raw primitive safe here")

    def check(self, ctx: FileContext) -> None:
        if not ctx.in_package_scope(("quickwit_tpu/",)):
            return
        if ctx.relpath.endswith("common/sync.py"):
            return  # the seam itself: raw construction is its job
        from_imports: set[str] = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ImportFrom) and node.level == 0 \
                    and node.module == "threading":
                from_imports.update(
                    a.asname or a.name for a in node.names
                    if a.name in _QW008_CTORS)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            dotted = dotted_name(func)
            if (isinstance(func, ast.Attribute)
                    and isinstance(func.value, ast.Name)
                    and func.value.id == "threading"
                    and func.attr in _QW008_CTORS):
                ctx.add(self.id, node,
                        self._message(f"threading.{func.attr}()"))
            elif (isinstance(func, ast.Name) and dotted in from_imports):
                ctx.add(self.id, node, self._message(f"{dotted}()"))


RULES = [HiddenHostReadback(), RecompilationHazard(),
         AmbientContextPropagation(), SwallowedControlFlow(),
         MetricsHygiene(), AmbientTimeAndRandomness(), LockOrder(),
         RawThreadingConstruction()]

RULE_DOCS = {rule.id: rule.title for rule in RULES}
