"""Rule `unguarded-collective`: collectives outside the watchdog contract.

PR 11's contract: the jax runtime has no per-collective timeout, so a
dead or wedged peer leaves every other rank blocked FOREVER inside the
next collective. Every HOST-LEVEL collective dispatch must therefore be
armed with `watchdog.deadline(site)` — on expiry the rank dumps stacks,
writes rank-failure evidence, and exits rc 113 instead of hanging.

What the rule checks:

- `jax.experimental.multihost_utils.process_allgather(...)` — the raw
  host collective — must sit lexically inside a `with
  watchdog.deadline(...)` block (or in a function whose every in-module
  call site does; see astutil.ModuleIndex.covered_functions).
- calling a shard_map-produced function (a local name assigned from
  `shard_map(...)` / `jax.shard_map(...)`, or from the learners'
  `_sharded_grower(...)` builder that jits one) is
  a host-level dispatch of a program whose collectives can block on a
  peer: same deadline requirement, same interprocedural coverage (the
  learners.py idiom — `__call__` arms the deadline, `_dispatch` runs
  the shard-mapped program).
- `jax.lax.psum` / `psum_scatter` / `all_gather` / `pmax` / `pmin` /
  `pmean` / `all_to_all` / `ppermute` are DEVICE-level collectives that
  are only legal while tracing; they must appear in a traced context
  (jit/shard_map-decorated or -wrapped function, or a helper reachable
  from one through the module-local call graph). Anywhere else they are
  a host-level dispatch with no watchdog — or a bug outright.

`multihost.allgather_bytes` / `agree_on_iteration` are exempt by
design: they arm the deadline INTERNALLY (that is the module's whole
point), so call sites need no second guard.
"""
from __future__ import annotations

import ast
from typing import Iterable, List, Set

from ..core import Finding, Rule, SourceFile
from .. import astutil
from ..astutil import ModuleIndex, call_target, dotted_name

LAX_COLLECTIVES = {"psum", "psum_scatter", "all_gather", "pmax", "pmin",
                   "pmean", "all_to_all", "ppermute", "pshuffle"}
HOST_COLLECTIVES = {"process_allgather"}
SHARD_MAP_MAKERS = {"shard_map", "_sharded_grower"}

# traced-only functions the AST cannot see get jitted: ops/predict.py's
# forest kernels are wrapped via jax.jit(getattr(predict_ops, name)) in
# boosting/gbdt.py (`_forest_jit`)
KNOWN_TRACED = (
    (r"ops/predict\.py$", r"^predict_forest_"),
)


class UnguardedCollectiveRule(Rule):
    name = "unguarded-collective"
    description = ("host-level collective dispatch outside a "
                   "watchdog.deadline() guard (hangs forever on a dead "
                   "peer), or a device collective outside traced code")

    def check_file(self, src: SourceFile) -> Iterable[Finding]:
        out: List[Finding] = []
        idx = ModuleIndex(src.tree, src.display_path,
                          known_traced=KNOWN_TRACED)
        is_deadline = astutil.deadline_guard(idx.imports)
        covered = idx.covered_functions(is_deadline)
        traced = idx.traced_functions()

        # local names bound to shard_map-produced callables, per
        # enclosing function (run = shard_map(f, ...); run(...))
        sharded_names: Set[ast.AST] = set()  # the Assign nodes
        shard_bound: dict = {}  # (enclosing_fn, name) -> assign node
        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Assign):
                continue
            if not isinstance(node.value, ast.Call):
                continue
            target_fn = call_target(node.value, idx.imports)
            if target_fn is None or \
                    target_fn.split(".")[-1] not in SHARD_MAP_MAKERS:
                continue
            encs = astutil.enclosing_functions(node, idx.parents)
            enc = encs[0] if encs else None
            for t in node.targets:
                if isinstance(t, ast.Name):
                    shard_bound[(enc, t.id)] = node
                    sharded_names.add(node)

        for node in ast.walk(src.tree):
            if not isinstance(node, ast.Call):
                continue
            target = call_target(node, idx.imports)
            tail = target.split(".")[-1] if target else None

            if tail in HOST_COLLECTIVES:
                if not idx.guarded(node, is_deadline, covered):
                    out.append(src.finding(
                        self.name, node,
                        "%s is a host-level collective and must run "
                        "under 'with watchdog.deadline(site)': a dead "
                        "peer otherwise blocks this rank forever "
                        "(PR 11 contract)" % tail))
                continue

            if tail in LAX_COLLECTIVES and target and \
                    ("lax" in target.split(".") or
                     target.split(".")[0] == "jax"):
                encs = astutil.enclosing_functions(node, idx.parents)
                if not any(f in traced for f in encs):
                    out.append(src.finding(
                        self.name, node,
                        "jax.lax.%s outside any traced (jit/shard_map) "
                        "context: device collectives only execute under "
                        "a trace, and the host dispatch that runs them "
                        "must be watchdog-armed" % tail))
                continue

            # dispatch of a shard_map-produced callable
            if isinstance(node.func, ast.Name):
                encs = astutil.enclosing_functions(node, idx.parents)
                enc = encs[0] if encs else None
                bound = shard_bound.get((enc, node.func.id))
                if bound is not None and \
                        not idx.guarded(node, is_deadline, covered):
                    out.append(src.finding(
                        self.name, node,
                        "dispatch of shard_map-produced %r outside "
                        "'with watchdog.deadline(site)': the program's "
                        "collectives block forever on a dead peer "
                        "(PR 11 contract)" % node.func.id))
        return out
