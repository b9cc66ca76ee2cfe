"""HS301/HS302 — lock-discipline race detector.

A frozen registry names the process-shared mutable state the 8-thread
serving path can hit concurrently, in two shapes:

- **classes** (:data:`LOCK_CLASSES`): instance attributes that must only
  be mutated lexically inside ``with self.<lock>`` (``__init__`` is
  construction and exempt; *delegating methods* — helpers documented to
  run with the lock already held by every caller — are registered
  per-class and count as frozen exemptions with a printed
  justification);
- **module-global groups** (:data:`LOCK_GLOBALS`): module-level
  counters/registries that must only be mutated inside ``with <lock>``
  (their module-top initialization is exempt).

Findings: a plain unguarded mutation is **HS301**; an unguarded
compound read-modify-write (``x += 1``, ``self.n = self.n + d`` — the
shape that LOSES updates under contention, r11's audit class) is
**HS302**. Both carry the registered lock as the related site.

The registry is FROZEN the same way the span/fault-name registries are:
additions need a justification string (printed by
``scripts/lint.py --exemptions``) and a test; entries that stop
matching real code surface as HS004 (unused exemption).
"""

from __future__ import annotations

import ast
from typing import List

from . import dataflow as df
from .diagnostics import Diagnostic, Related

# (slash rel, class name) -> rule. ``locks`` maps a lock attribute to
# the attribute names it guards (None = every instance attribute).
# ``delegates`` are methods whose callers all hold the lock already.
LOCK_CLASSES = {
    ("hyperspace_tpu/serving/program_bank.py", "ProgramBank"): {
        "locks": {"_lock": None},
        "delegates": frozenset(),
        "why": "THE cross-session compiled-program registry; every "
               "serving worker's lookup mutates its LRU + counters",
    },
    ("hyperspace_tpu/serving/result_cache.py", "ResultCache"): {
        "locks": {"_lock": None},
        "delegates": frozenset({"_drop", "_pop_device_victims",
                                "_pop_host_victims"}),
        "why": "three-tier result cache shared by every query thread; "
               "the delegates are eviction helpers every caller invokes "
               "under the lock (their docstrings say 'Under the lock')",
    },
    ("hyperspace_tpu/telemetry/metrics.py", "MetricsRegistry"): {
        "locks": {"_lock": None},
        "delegates": frozenset(),
        "why": "process-wide metrics registry; push-side feeds come "
               "from arbitrary threads",
    },
    ("hyperspace_tpu/telemetry/metrics.py", "SlidingHistogram"): {
        "locks": {"_lock": None},
        "delegates": frozenset(),
        "why": "serving latency histogram; record() runs per completed "
               "query on worker threads",
    },
    ("hyperspace_tpu/serving/frontend.py", "ServingFrontend"): {
        "locks": {"_lock": None},
        "delegates": frozenset({"_collect_batch"}),
        "why": "admission queue + stats shared by submitters and the "
               "drain workers; _collect_batch documents 'Under the "
               "lock' and is only called with it held",
    },
    ("hyperspace_tpu/serving/context.py", "QueryContext"): {
        "locks": {"_io_lock": {"_io", "_cancel_emitted"}},
        "delegates": frozenset(),
        "why": "per-query io counters are written by prefetch producers "
               "on other threads (copied contexts)",
    },
    ("hyperspace_tpu/cluster/worker.py", "ClusterNode"): {
        "locks": {"_lock": None},
        "delegates": frozenset(),
        "why": "forward/broadcast stats are bumped by the submit path, "
               "the server's connection threads, and the heartbeat — "
               "three thread families over one counter dict",
    },
    ("hyperspace_tpu/cluster/gather.py", "_GatherHub"): {
        "locks": {"_cond": None},
        "delegates": frozenset(),
        "why": "rendezvous slots are filled by one connection thread "
               "per rank; the condition is both the mutex and the "
               "all-parts-arrived wakeup",
    },
    ("hyperspace_tpu/robustness/faults.py", "FaultRegistry"): {
        "locks": {"_lock": {"_hits", "_fired"}},
        "delegates": frozenset(),
        "why": "one armed registry is shared across a submission wave; "
               "nth/times counters must not tear",
    },
    ("hyperspace_tpu/robustness/faults.py", "_Stats"): {
        "locks": {"_lock": {"_counts"}},
        "delegates": frozenset(),
        "why": "process-lifetime robustness counters, bumped from "
               "workers and degradation ladders",
    },
    ("hyperspace_tpu/parallel/sharding.py", "MeshProgram"): {
        "locks": {"_lock": {"_compiled"}},
        "delegates": frozenset(),
        "why": "AOT program map; two sessions can race the same stage's "
               "first compile",
    },
    ("hyperspace_tpu/streaming/ingest.py", "CommitQueue"): {
        "locks": {"_lock": None},
        "delegates": frozenset(),
        "why": "process-wide staged-batch registry of the ingestion "
               "tier; appends/commits race from serving workers",
    },
    ("hyperspace_tpu/streaming/ingest.py", "CommitCoordinator"): {
        "locks": {"_cv": None},
        "delegates": frozenset(),
        "why": "group-commit wave ledger; concurrent committers elect "
               "a leader and park as riders on the one condition",
    },
    ("hyperspace_tpu/streaming/sources.py", "ContinuousSource"): {
        "locks": {"_lock": None},
        "delegates": frozenset(),
        "why": "tailer daemon mutates pending/stats while stop()/"
               "stats() read from caller threads",
    },
    ("hyperspace_tpu/streaming/sources.py", "DirectoryTailSource"): {
        "locks": {"_lock": None},
        "delegates": frozenset(),
        "why": "consumed-name set shared between the poll loop and "
               "discovery",
    },
    ("hyperspace_tpu/streaming/sources.py", "LogTailSource"): {
        "locks": {"_lock": None},
        "delegates": frozenset(),
        "why": "consumed byte offset advances on the daemon while "
               "stats() reads",
    },
    ("hyperspace_tpu/streaming/subscriptions.py", "SubscriptionRegistry"): {
        "locks": {"_lock": None},
        "delegates": frozenset(),
        "why": "standing-query table; subscribes race commit-time fires",
    },
    ("hyperspace_tpu/streaming/subscriptions.py", "Subscription"): {
        "locks": {"_cv": None},
        "delegates": frozenset(),
        "why": "deliveries append from serving worker completion "
               "callbacks while consumers poll",
    },
    ("hyperspace_tpu/telemetry/flight_recorder.py", "FlightRecorder"): {
        "locks": {"_lock": None},
        "delegates": frozenset(),
        "why": "process-wide anomaly rings fed by every event "
               "construction and trace retention across worker threads",
    },
    ("hyperspace_tpu/telemetry/slo.py", "SloMonitor"): {
        "locks": {"_lock": None},
        "delegates": frozenset(),
        "why": "sliding SLO window fed per completed query from "
               "serving workers; breach edge state must not tear",
    },
    ("hyperspace_tpu/adaptive/feedback.py", "CorrectionStore"): {
        "locks": {"_lock": None},
        "delegates": frozenset(),
        "why": "process-wide cardinality correction store; executors "
               "observe() from serving workers while reorders read",
    },
    ("hyperspace_tpu/adaptive/admission.py", "AdmissionController"): {
        "locks": {"_lock": None},
        "delegates": frozenset(),
        "why": "process-wide overload verdict + tallies; submits race "
               "from client threads against the rate-limited refresh",
    },
    ("hyperspace_tpu/adaptive/builder.py", "BuilderLedger"): {
        "locks": {"_lock": None},
        "delegates": frozenset(),
        "why": "builder accounting shared by the daemon loop, explicit "
               "run_once callers, and stats readers",
    },
    ("hyperspace_tpu/artifacts/store.py", "ArtifactStore"): {
        "locks": {"_lock": None},
        "delegates": frozenset({"_load_usage_locked"}),
        "why": "one store per lake root shared by every session over "
               "it; hit/miss/persist counters and the usage tallies are "
               "bumped from concurrent serving workers; "
               "_load_usage_locked runs at construction, before the "
               "store escapes __init__",
    },
    ("hyperspace_tpu/artifacts/manager.py", "ArtifactManager"): {
        "locks": {"_lock": {"_loaded", "warm_hits", "preloaded",
                            "preload_ms", "preload_bytes"},
                  "_util_lock": {"_util"}},
        "delegates": frozenset(),
        "why": "per-root executable cache probed by every dispatch "
               "seam while the boot preloader populates it; the "
               "utility-kernel map has its own lock (ordering: "
               "_util_lock -> _lock, never reversed)",
    },
    ("hyperspace_tpu/artifacts/manager.py", "AotStage"): {
        "locks": {"_lock": {"_compiled"}},
        "delegates": frozenset(),
        "why": "bank stages are process-shared; two serving workers "
               "can race one signature's first AOT acquire",
    },
    ("hyperspace_tpu/artifacts/manager.py", "_ManagerRegistry"): {
        "locks": {"_lock": {"_by_root"}},
        "delegates": frozenset(),
        "why": "double-checked per-root manager construction",
    },
    ("hyperspace_tpu/execution/buffer_pool.py", "BufferPool"): {
        "locks": {"_lock": None},
        "delegates": frozenset({"_bump_ns", "_drop",
                                "_pop_device_victims",
                                "_pop_host_victims"}),
        "why": "THE process-wide tiered scan-buffer cache; every query "
               "thread's probe mutates two LRU tiers + counters, and "
               "the delegates are under-lock helpers (their docstrings "
               "say 'Under the lock') whose demote/promote conversions "
               "the callers run outside it",
    },
    ("hyperspace_tpu/index/log_manager.py", "LogLookupCache"): {
        "locks": {"_lock": None},
        "delegates": frozenset(),
        "why": "process-wide op-log lookup memo probed per query per "
               "index on the serving hot path",
    },
    ("hyperspace_tpu/session.py", "Session"): {
        "locks": {"_views_lock": {"_temp_views", "_temp_views_version"},
                  "_join_actuals_lock": {"_join_actuals"},
                  "_sql_plan_lock": {"_sql_plan_cache",
                                     "_sql_plan_stats"},
                  "_usage_counts_lock": {"_index_usage_counts"}},
        "delegates": frozenset(),
        "why": "sessions are shared by serving workers; these four "
               "stores are the documented multi-thread surfaces (r11 "
               "thread-safety audit)",
    },
}

# slash rel -> [{lock, names, why}]: module globals that serving-path
# code mutates. The lock spec is a dotted name as written at the with
# site ("_COUNT_LOCK", "_STATE.lock").
LOCK_GLOBALS = {
    "hyperspace_tpu/parallel/io.py": [
        {"lock": "_pool_lock", "names": {"_pool", "_pool_size"},
         "why": "reader-pool grow-only replacement races submits"},
        {"lock": "_serving_lock",
         "names": {"_serving_pool", "_serving_pool_size"},
         "why": "serving-pool grow-only replacement races submits"},
        {"lock": "_stats_lock", "names": {"_STATS"},
         "why": "process io counters are bumped per pooled read"},
    ],
    "hyperspace_tpu/serving/frontend.py": [
        {"lock": "_DEFAULT_LOCK", "names": {"_DEFAULT"},
         "why": "first-constructed frontend becomes the process "
                "default exactly once"},
    ],
    "hyperspace_tpu/serving/program_bank.py": [
        {"lock": "_BANK_LOCK", "names": {"_BANK"},
         "why": "double-checked singleton construction"},
    ],
    "hyperspace_tpu/execution/buffer_pool.py": [
        {"lock": "_POOL_LOCK", "names": {"_POOL"},
         "why": "double-checked singleton construction"},
    ],
    "hyperspace_tpu/streaming/ingest.py": [
        {"lock": "_QUEUE_LOCK", "names": {"_QUEUE"},
         "why": "double-checked singleton construction"},
        {"lock": "_COORD_LOCK", "names": {"_COORD"},
         "why": "double-checked singleton construction"},
    ],
    "hyperspace_tpu/telemetry/metrics.py": [
        {"lock": "_REGISTRY_LOCK", "names": {"_REGISTRY"},
         "why": "double-checked singleton construction"},
    ],
    "hyperspace_tpu/artifacts/manager.py": [
        {"lock": "_REGISTRY_LOCK", "names": {"_REGISTRY"},
         "why": "double-checked singleton construction"},
    ],
    "hyperspace_tpu/cluster/worker.py": [
        {"lock": "_NODE_LOCK", "names": {"_NODE"},
         "why": "double-checked singleton construction"},
    ],
    "hyperspace_tpu/cluster/gather.py": [
        {"lock": "_HUB_LOCK",
         "names": {"_HUB", "_SEQ", "_NATIVE_OK", "_FORCED"},
         "why": "rank-0 hub construction, the gather sequence counter, "
                "and the cached native-collectives verdict are all "
                "touched from concurrent gather callers"},
    ],
    "hyperspace_tpu/parallel/sharding.py": [
        {"lock": "_COUNT_LOCK",
         "names": {"COMPILE_COUNT", "DISPATCH_COUNT"},
         "why": "mesh compile/dispatch tallies are asserted exact by "
                "tests and bumped from concurrent serving workers"},
    ],
    "hyperspace_tpu/execution/spmd.py": [
        {"lock": "_COUNT_LOCK",
         "names": {"DISPATCH_COUNT", "SORT_DISPATCH_COUNT",
                   "LAST_CAP_ATTEMPTS"},
         "why": "SPMD dispatch tallies (explain/bench read them; "
                "serving workers bump them concurrently)"},
    ],
    "hyperspace_tpu/parallel/distributed_build.py": [
        {"lock": "_COUNT_LOCK", "names": {"DISPATCH_COUNT"},
         "why": "distributed-build dispatch tally"},
    ],
    "hyperspace_tpu/execution/fusion.py": [
        {"lock": "_STATE.lock", "names": {"DISPATCH_COUNT"},
         "why": "fused-execution tally lives beside the _FusionState "
                "counters its stats() reports it with"},
    ],
    "hyperspace_tpu/execution/executor.py": [
        {"lock": "_CHUNK_STATS_LOCK", "names": {"CHUNK_SCAN_STATS"},
         "why": "chunked-scan watermark counters; serving workers "
                "stream chunks concurrently"},
    ],
    "hyperspace_tpu/ops/index_build.py": [
        {"lock": "_CHUNK_STATS_LOCK", "names": {"CHUNK_STATS"},
         "why": "chunked-build watermark counters; concurrent actions "
                "build indexes in parallel"},
    ],
    "hyperspace_tpu/execution/shapes.py": [
        {"lock": "_counter_lock",
         "names": {"_compile_total", "_compile_seconds",
                   "_listener_installed"},
         "why": "the backend-compile counter fires from any thread "
                "that triggers an XLA compile"},
    ],
}


def exemption_ids() -> dict:
    """Delegate-method exemptions, for the HS004 unused-entry check."""
    out = {}
    for (rel, cls), rule in LOCK_CLASSES.items():
        for meth in rule["delegates"]:
            out[f"{rel}#lock-delegate:{cls}.{meth}"] = rule["why"]
    return out


def describe_exemptions() -> List[str]:
    out = []
    for (rel, cls), rule in sorted(LOCK_CLASSES.items()):
        locks = ", ".join(sorted(rule["locks"]))
        out.append(f"lock[{rel} {cls} via {locks}]: {rule['why']}")
        for meth in sorted(rule["delegates"]):
            out.append(f"  delegate {cls}.{meth}: callers hold the lock")
    for rel, groups in sorted(LOCK_GLOBALS.items()):
        for g in groups:
            names = ", ".join(sorted(g["names"]))
            out.append(f"lock[{rel} globals {names} via {g['lock']}]: "
                       f"{g['why']}")
    return out


def _is_rmw(node, attr_or_name: str, self_attr: bool) -> bool:
    if isinstance(node, ast.AugAssign):
        return True
    if isinstance(node, ast.Assign):
        reads = df.reads_attr if self_attr else df.reads_name
        return reads(node.value, attr_or_name)
    return False


def _mutations_in(func_node, own_only: bool = False):
    """(node, attr-or-None, global-name-or-None, is_call) mutation sites
    in a function body. ``own_only`` skips nested defs (the module-
    global scan visits those through their own FuncInfo)."""
    out = []
    nodes = df.walk_own(func_node) if own_only else ast.walk(func_node)
    for node in nodes:
        if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                a = df.self_attr_of_target(t)
                if a is not None:
                    out.append((node, a, None, False))
                    continue
                g = None
                # Plain `x = ...` rebinding a local is not a global
                # mutation; `x[k] = ...` through a registered global is.
                if isinstance(t, ast.Subscript):
                    g = df.global_name_of_target(t)
                elif isinstance(t, ast.Name):
                    g = t.id
                if g is not None:
                    out.append((node, None, g, False))
        elif isinstance(node, ast.Delete):
            for t in node.targets:
                a = df.self_attr_of_target(t)
                if a is not None:
                    out.append((node, a, None, False))
                else:
                    g = df.global_name_of_target(t)
                    if g is not None:
                        out.append((node, None, g, False))
        elif isinstance(node, ast.Call) \
                and isinstance(node.func, ast.Attribute) \
                and node.func.attr in df.MUTATOR_METHODS:
            recv = node.func.value
            a = df.self_attr_of_target(recv)
            if a is not None:
                out.append((node, a, None, True))
            else:
                g = df.global_name_of_target(recv)
                if g is not None:
                    out.append((node, None, g, True))
    return out


def _globals_declared(func_node) -> set:
    out = set()
    for node in ast.walk(func_node):
        if isinstance(node, ast.Global):
            out.update(node.names)
    return out


def check_file(src, ctx) -> List[Diagnostic]:
    out: List[Diagnostic] = []
    rel = src.rel
    slash = src.slash_rel
    class_rules = {cls: rule for (r, cls), rule in LOCK_CLASSES.items()
                   if r == slash}
    global_groups = LOCK_GLOBALS.get(slash, [])
    if not class_rules and not global_groups:
        return out
    idx = src.index

    # -- registered classes -------------------------------------------
    for cls_node in idx.of(ast.ClassDef):
        rule = class_rules.get(cls_node.name)
        if rule is None:
            continue
        lock_specs = ["self." + lk for lk in rule["locks"]]
        attr_to_lock = {}
        catch_all = None
        for lk, attrs in rule["locks"].items():
            if attrs is None:
                catch_all = lk
            else:
                for a in attrs:
                    attr_to_lock[a] = lk
        for meth in cls_node.body:
            if not isinstance(meth, df.FUNC_TYPES):
                continue
            if meth.name == "__init__":
                continue
            if meth.name in rule["delegates"]:
                ctx.note_exemption(
                    f"{slash}#lock-delegate:{cls_node.name}.{meth.name}")
                continue
            # A nested def/lambda lexically under the with-lock does
            # NOT run under it (it's a deferred callable) — so each
            # function body gets its OWN guard set and own-statements
            # scan, exactly like the module-global pass.
            for fn_node, guarded in _method_scopes(meth, lock_specs):
                _check_method_scope(out, rel, cls_node, rule, meth,
                                    attr_to_lock, catch_all, fn_node,
                                    guarded)
    _check_global_groups(out, src, rel, global_groups)
    return out


def _method_scopes(meth, lock_specs):
    """(function node, guard set) for a method and every nested
    def/lambda inside it. Each scope is guard-computed from its own
    subtree and mutation-scanned own-statements-only, so a with-lock in
    an ENCLOSING scope never guards a deferred callable's body (the
    callable runs later, unlocked) — the module-global pass's
    walk_own contract, applied to classes."""
    scopes = [meth]
    for node in ast.walk(meth):
        if isinstance(node, df.FUNC_TYPES + (ast.Lambda,)) \
                and node is not meth:
            scopes.append(node)
    return [(fn, df.guarded_node_ids(fn, lock_specs)) for fn in scopes]


def _check_method_scope(out, rel, cls_node, rule, meth, attr_to_lock,
                        catch_all, fn_node, guarded) -> None:
    for node, attr, _g, is_call in _mutations_in(fn_node,
                                                 own_only=True):
        if attr is None:
            continue
        lock = attr_to_lock.get(attr, catch_all)
        if lock is None:
            continue  # attribute outside every guarded group
        if id(node) in guarded:
            continue
        rmw = not is_call and _is_rmw(node, attr, True)
        kind = "read-modify-write loses updates" if rmw \
            else "unguarded shared-state mutation"
        out.append(Diagnostic(
            "HS302" if rmw else "HS301", rel, node.lineno,
            f"{cls_node.name}.{meth.name} mutates "
            f"self.{attr} outside 'with self.{lock}' "
            f"({kind}; registered shared-state class)",
            col=node.col_offset,
            related=Related(rel, cls_node.lineno,
                            f"register: {rule['why']}")))


def _check_global_groups(out, src, rel, global_groups) -> None:
    # -- registered module-global groups ------------------------------
    if global_groups:
        funcs = df.function_map(src.tree)
        name_to_group = {}
        for g in global_groups:
            for n in g["names"]:
                name_to_group[n] = g
        for info in funcs.values():
            declared = _globals_declared(info.node)
            guard_cache = {}
            for node, _attr, gname, is_call in _mutations_in(
                    info.node, own_only=True):
                if gname is None or gname not in name_to_group:
                    continue
                grp = name_to_group[gname]
                # A bare `x = ...` in a function only mutates the global
                # when declared global; subscript/mutator writes always
                # reach the module object.
                if not is_call and isinstance(node, (ast.Assign,
                                                     ast.AnnAssign,
                                                     ast.AugAssign)):
                    plain_name = any(
                        isinstance(t, ast.Name)
                        for t in (node.targets if isinstance(
                            node, ast.Assign) else [node.target]))
                    if plain_name and gname not in declared:
                        continue
                lock = grp["lock"]
                if lock not in guard_cache:
                    guard_cache[lock] = df.guarded_node_ids(
                        info.node, [lock])
                if id(node) in guard_cache[lock]:
                    continue
                rmw = not is_call and _is_rmw(node, gname, False)
                kind = "read-modify-write loses updates" if rmw \
                    else "unguarded shared-state mutation"
                out.append(Diagnostic(
                    "HS302" if rmw else "HS301", rel, node.lineno,
                    f"{info.qualname} mutates module global "
                    f"'{gname}' outside 'with {lock}' "
                    f"({kind}; registered shared-state group)",
                    col=node.col_offset,
                    related=Related(rel, node.lineno, grp["why"])))
