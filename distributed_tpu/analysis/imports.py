"""Rule ``jax-free-import``: declared jax-free modules must stay jax-free
TRANSITIVELY at module scope.

The framework's controller-side surfaces — the supervisor, the obs
registry/aggregation/CLI, the fleet router, the event transport — carry
"jax-free at import" contracts in their docstrings: they must be cheap
to import on controller/CI processes and unit-testable with jax
monkeypatched out. Before this rule the contract was prose asserted in
~15 docstrings and broken silently: a module three hops down adds one
top-level ``import jax`` and every "jax-free" importer above it now
pays (and requires) the jax world.

Mechanics: every file's MODULE-SCOPE imports (top-level statements,
recursing into if/try/with/class bodies — all execute at import — but
never into function bodies) become graph edges. ``from pkg import sub``
resolves to the submodule when one exists in the scanned tree, else to
``pkg`` (its ``__init__`` defines the symbol, and runs). The rule walks
the closure from each manifest module and reports the full chain to
``jax``/``jaxlib`` when one exists.

Scope note: ancestor-package ``__init__`` execution is deliberately NOT
an edge (importing ``a.b.c`` runs ``a/__init__``). The top-level
``distributed_tpu/__init__`` eagerly builds the training world, so the
file-level graph is the contract these modules can actually keep — it
bounds what the MODULE ITSELF drags in, which is what jax-out
unit tests and import-cost budgets observe.

The manifest below is the declared list; ``dtpu-lint --jax-free mod``
appends entries for one run (fixture trees in tests use this).
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Sequence, Tuple

from .core import Finding, SourceTree, iter_module_scope, register

POISON = ("jax", "jaxlib")

#: Modules contractually jax-free at import. Grow this list whenever a
#: docstring claims jax-freeness — the claim is only real once it is
#: machine-checked here.
JAX_FREE_MODULES: Tuple[str, ...] = (
    # observability: importable on controller boxes next to the event log
    "distributed_tpu.obs",
    "distributed_tpu.obs.aggregate",
    "distributed_tpu.obs.cli",
    "distributed_tpu.obs.compile_ledger",
    "distributed_tpu.obs.export",
    "distributed_tpu.obs.flight",
    "distributed_tpu.obs.registry",
    "distributed_tpu.obs.spans",
    # event transport + schema + logging
    "distributed_tpu.utils.compile_cache",
    "distributed_tpu.utils.event_schema",
    "distributed_tpu.utils.events",
    "distributed_tpu.utils.logging",
    # resilience controller side (the supervisor runs where jax may not)
    "distributed_tpu.resilience.elastic",
    "distributed_tpu.resilience.markers",
    "distributed_tpu.resilience.policy",
    "distributed_tpu.resilience.supervisor",
    # fleet control plane (pure host arithmetic)
    "distributed_tpu.fleet.autoscale",
    "distributed_tpu.fleet.router",
    # gang launcher + the pieces it stands on
    "distributed_tpu.cluster.config",
    "distributed_tpu.cluster.net",
    "distributed_tpu.launch.core",
    "distributed_tpu.serving.scheduler",
    # serving service router side (the router process never pays a jax
    # import; serve_service.worker is the ONE jax module and is spawned,
    # never imported, by these)
    "distributed_tpu.serve_service",
    "distributed_tpu.serve_service.protocol",
    "distributed_tpu.serve_service.quotas",
    "distributed_tpu.serve_service.service",
    "distributed_tpu.serve_service.transport",
    # the linter itself
    "distributed_tpu.analysis",
    "distributed_tpu.analysis.cli",
    "distributed_tpu.analysis.core",
    "distributed_tpu.analysis.events",
    "distributed_tpu.analysis.imports",
    "distributed_tpu.analysis.purity",
    "distributed_tpu.analysis.threads",
)


def module_scope_imports(sf) -> List[Tuple[str, int]]:
    """``(dotted-target, lineno)`` per module-scope import of ``sf``,
    resolved to absolute dotted names (relative levels applied)."""
    is_init = sf.path.name == "__init__.py"
    pkg_parts = sf.module.split(".") if sf.module else []
    if not is_init:
        pkg_parts = pkg_parts[:-1]  # containing package
    out: List[Tuple[str, int]] = []
    for node in iter_module_scope(sf.tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                out.append((alias.name, node.lineno))
        elif isinstance(node, ast.ImportFrom):
            if node.level == 0:
                base = node.module or ""
            else:
                up = pkg_parts[: len(pkg_parts) - (node.level - 1)]
                base = ".".join(up + ([node.module] if node.module else []))
            if not base:
                continue
            for alias in node.names:
                out.append((f"{base}.{alias.name}", node.lineno))
    return out


class ImportGraph:
    """Module-scope import edges over a SourceTree, with resolution:
    ``pkg.sub`` that exists as a scanned module stays itself; ``pkg.sym``
    (a symbol import) falls back to ``pkg``; anything outside the tree
    collapses to its top-level name (``jax.numpy`` -> ``jax``)."""

    def __init__(self, tree: SourceTree):
        self.tree = tree
        self.edges: Dict[str, List[Tuple[str, int]]] = {}
        for sf in tree.files:
            deps: List[Tuple[str, int]] = []
            for target, lineno in module_scope_imports(sf):
                deps.append((self._resolve(target), lineno))
            self.edges[sf.module] = deps

    def _resolve(self, dotted: str) -> str:
        if dotted in self.tree.by_module:
            return dotted
        parts = dotted.split(".")
        for cut in range(len(parts) - 1, 0, -1):
            cand = ".".join(parts[:cut])
            if cand in self.tree.by_module:
                return cand
        return parts[0]  # external: top-level distribution name

    def chain_to(self, start: str,
                 targets: Sequence[str]) -> Optional[List[str]]:
        """Shortest module chain ``[start, ..., target]`` reaching any of
        ``targets`` through module-scope imports, else None."""
        if start not in self.edges:
            return None
        parent: Dict[str, Optional[str]] = {start: None}
        queue = [start]
        while queue:
            cur = queue.pop(0)
            for dep, _ in self.edges.get(cur, ()):
                if dep in parent:
                    continue
                parent[dep] = cur
                if dep in targets:
                    chain = [dep]
                    at: Optional[str] = cur
                    while at is not None:
                        chain.append(at)
                        at = parent[at]
                    return list(reversed(chain))
                queue.append(dep)
        return None

    def first_hop_line(self, start: str, nxt: str) -> int:
        for dep, lineno in self.edges.get(start, ()):
            if dep == nxt:
                return lineno
        return 1


@register
class JaxFreeImportRule:
    """See module docstring."""

    name = "jax-free-import"

    def __init__(self, manifest: Optional[Sequence[str]] = None,
                 extra_manifest: Sequence[str] = ()):
        base = tuple(manifest) if manifest is not None else JAX_FREE_MODULES
        self.manifest = tuple(base) + tuple(extra_manifest)

    def check(self, tree: SourceTree) -> List[Finding]:
        graph = ImportGraph(tree)
        tops = {m.split(".")[0] for m in tree.by_module if m}
        findings: List[Finding] = []
        for mod in self.manifest:
            sf = tree.by_module.get(mod)
            if sf is None:
                # Only a full scan of the module's package can judge a
                # missing entry (partial/fixture scans skip silently).
                if mod.split(".")[0] in tops:
                    findings.append(Finding(
                        self.name, "<manifest>", 1,
                        f"manifest names unknown module '{mod}' "
                        f"(typo, or the file moved without updating "
                        f"analysis/imports.py)",
                    ))
                continue
            chain = graph.chain_to(mod, POISON)
            if chain is None:
                continue
            line = graph.first_hop_line(mod, chain[1]) if len(chain) > 1 \
                else 1
            findings.append(Finding(
                self.name, sf.rel, line,
                f"module '{mod}' is declared jax-free at import but its "
                f"module-scope imports reach '{chain[-1]}' via "
                + " -> ".join(chain[1:])
                + " (defer the import into the function that needs it, "
                  "or remove the module from the manifest)",
            ))
        return findings
