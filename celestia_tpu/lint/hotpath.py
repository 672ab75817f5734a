"""R7 ``host-sync`` and R8 ``layering``: hot-path and architecture rules.

**R7** keeps implicit device→host synchronization out of the extend hot
path.  PAPERS.md 2108.02692's lesson — the kernel pipeline is only as
fast as its slowest serializing host round-trip — became mechanical
telemetry in PR 11 (devprof dispatch brackets); this rule is the
enforcement half: in ``da/``, ``ops/`` and ``state/`` a device value may
only cross to the host through a devprof ``dispatch()`` bracket (whose
``done()`` drains the device ON the profiled timeline) or a per-line
allow with a reason.  Banned forms: ``.item()``, bare
``block_until_ready``, and ``np.asarray``/``np.array``/``float``/
``int``/``bool`` applied to a value the rule can infer is device-
resident (assigned from a ``jnp.*`` call, ``jax.device_put``, or a call
through a jitted-program handle — a name bound from ``jax.jit(...)`` or
a ``*_fn``/``*_jit`` program factory).  Inference is deliberately
conservative: attribute chains and unresolved calls are not tainted —
missing a sync is a known cost, flagging a host-only numpy path would
teach people to sprinkle allows.

**R8** enforces the package DAG so the sharding refactor cannot tangle
imports::

    appconsts → utils → ops → da → parallel → state → node → client → cli

An import (module-level OR lazy, inside a function) from a package at
the same or a higher layer is a back-edge finding.  ``lint`` sits above
everything and is imported by nothing in the tree.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from celestia_tpu.lint.engine import Finding, ModuleContext, Rule, register

# ---------------------------------------------------------------------------
# R7: host-sync
# ---------------------------------------------------------------------------

_HOT_PREFIXES = (
    "celestia_tpu/da/",
    "celestia_tpu/ops/",
    "celestia_tpu/state/",
)

_JIT_FACTORY_SUFFIXES = ("_fn", "_jit", "_JIT")


def _is_jit_factory_name(name: str) -> bool:
    return name.endswith(_JIT_FACTORY_SUFFIXES)


class _ScopeFacts:
    """Flow-insensitive per-function dataflow: which names hold device
    values, which hold devprof brackets, which were drained by a
    bracket's done()."""

    def __init__(self):
        self.tainted: Set[str] = set()
        self.brackets: Set[str] = set()
        self.jit_handles: Set[str] = set()
        self.drained: Set[str] = set()


@register
class HostSyncRule(Rule):
    id = "host-sync"
    summary = "no implicit device->host syncs in the da/ops/state hot path"
    doc = (
        "In celestia_tpu/{da,ops,state}/ flags .item(), bare "
        "block_until_ready, and np.asarray/np.array/float/int/bool on a "
        "value inferred device-resident (assigned from jnp.*, "
        "jax.device_put, or a jitted-program handle call) unless the "
        "value went through a devprof dispatch() bracket's done() — the "
        "one sanctioned drain.  Host round-trips "
        "serialize the device pipeline (2108.02692); every survivor must "
        "be deliberate."
    )

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        if not ctx.relpath.startswith(_HOT_PREFIXES):
            return
        aliases = _collect_aliases(ctx.tree)
        for scope_node in _scopes(ctx.tree):
            facts = _scope_facts(scope_node, aliases)
            yield from self._check_scope(ctx, scope_node, facts, aliases)

    def _check_scope(self, ctx, scope_node, facts, aliases) -> Iterator[Finding]:
        for node in _walk_scope(scope_node):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            # .item()
            if isinstance(f, ast.Attribute) and f.attr == "item" and not node.args:
                yield Finding(
                    self.id, ctx.relpath, node.lineno, node.col_offset,
                    ".item() forces a device->host sync per element — "
                    "fetch through a devprof dispatch() bracket (or batch "
                    "with jax.device_get) instead",
                )
                continue
            # bare block_until_ready
            if _is_block_until_ready(f):
                yield Finding(
                    self.id, ctx.relpath, node.lineno, node.col_offset,
                    "bare block_until_ready in the hot path — route the "
                    "dispatch through devprof.dispatch()/done(), which "
                    "drains the device on the profiled timeline",
                )
                continue
            # np.asarray/np.array/float/int/bool on an inferred device value
            sync_kind = _sync_call_kind(f, aliases)
            if sync_kind is None or len(node.args) < 1:
                continue
            arg = node.args[0]
            if (
                isinstance(arg, ast.Name)
                and arg.id in facts.tainted
                and arg.id not in facts.drained
            ):
                yield Finding(
                    self.id, ctx.relpath, node.lineno, node.col_offset,
                    f"{sync_kind}({arg.id}) implicitly syncs a device "
                    "value to the host outside a devprof dispatch() "
                    "bracket — wrap the dispatch (out = d.done(fn(x))) "
                    "or keep the value on-device",
                )


class _Aliases:
    def __init__(self):
        self.numpy: Set[str] = set()
        self.jnp: Set[str] = set()
        self.jax: Set[str] = set()
        self.devprof: Set[str] = set()
        self.dispatch_fns: Set[str] = set()  # from celestia_tpu... import dispatch


def _collect_aliases(tree: ast.AST) -> _Aliases:
    out = _Aliases()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                local = a.asname or a.name.split(".")[0]
                if a.name == "numpy":
                    out.numpy.add(local)
                elif a.name == "jax.numpy":
                    if a.asname is not None:
                        out.jnp.add(a.asname)
                    else:
                        # `import jax.numpy` binds the name `jax`; calls
                        # arrive as jax.numpy.<fn> (handled via the jax
                        # set + the dotted check), NOT as a jnp alias —
                        # putting "jax" in the jnp set would taint every
                        # jax.* call, including host-returning device_get
                        out.jax.add("jax")
                elif a.name == "jax":
                    out.jax.add(local)
        elif isinstance(node, ast.ImportFrom):
            if node.module == "jax":
                for a in node.names:
                    if a.name == "numpy":
                        out.jnp.add(a.asname or "numpy")
            elif node.module and node.module.startswith("celestia_tpu"):
                for a in node.names:
                    if a.name == "devprof":
                        out.devprof.add(a.asname or a.name)
                    elif a.name == "dispatch":
                        out.dispatch_fns.add(a.asname or a.name)
    return out


def _scopes(tree: ast.AST):
    """The module body and every function: one dataflow scope each."""
    yield tree
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


def _walk_scope(scope_node: ast.AST):
    """Walk a scope without descending into nested function defs (each
    function is its own dataflow scope)."""
    stack = list(ast.iter_child_nodes(scope_node))
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        yield node
        stack.extend(ast.iter_child_nodes(node))


def _scope_facts(scope_node: ast.AST, aliases: _Aliases) -> _ScopeFacts:
    facts = _ScopeFacts()
    nodes = list(_walk_scope(scope_node))
    # two passes so order of definition within the scope doesn't matter
    # (flow-insensitive: a name EVER drained is treated as drained)
    for _ in range(2):
        for node in nodes:
            if isinstance(node, ast.Assign):
                _note_assign(facts, node.targets, node.value, aliases)
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                _note_assign(facts, [node.target], node.value, aliases)
            elif isinstance(node, ast.Expr) and isinstance(node.value, ast.Call):
                _note_done_statement(facts, node.value)
    return facts


def _note_assign(facts, targets, value, aliases) -> None:
    names: List[str] = []
    for t in targets:
        if isinstance(t, ast.Name):
            names.append(t.id)
        elif isinstance(t, (ast.Tuple, ast.List)):
            names.extend(e.id for e in t.elts if isinstance(e, ast.Name))
    if not names:
        return
    if _is_device_producing(facts, value, aliases):
        facts.tainted.update(names)
    if _is_bracket_ctor(value, aliases):
        facts.brackets.update(names)
    if _is_jit_handle_ctor(value, aliases):
        facts.jit_handles.update(names)
    if _is_done_call(facts, value):
        facts.drained.update(names)
        _mark_done_arg(facts, value)
    # propagation: unpack/copy of an already-classified name
    if isinstance(value, ast.Name):
        if value.id in facts.drained:
            facts.drained.update(names)
        elif value.id in facts.tainted:
            facts.tainted.update(names)


def _note_done_statement(facts, call: ast.Call) -> None:
    if _is_done_call(facts, call):
        _mark_done_arg(facts, call)


def _mark_done_arg(facts, call: ast.Call) -> None:
    for arg in call.args:
        if isinstance(arg, ast.Name):
            facts.drained.add(arg.id)


def _is_done_call(facts, node: ast.AST) -> bool:
    return (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Attribute)
        and node.func.attr == "done"
        and isinstance(node.func.value, ast.Name)
        and node.func.value.id in facts.brackets
    )


def _is_bracket_ctor(node: ast.AST, aliases: _Aliases) -> bool:
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
        return f.value.id in aliases.devprof and f.attr == "dispatch"
    if isinstance(f, ast.Name):
        return f.id in aliases.dispatch_fns
    return False


def _is_jit_handle_ctor(node: ast.AST, aliases: _Aliases) -> bool:
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
        return f.value.id in aliases.jax and f.attr == "jit"
    if isinstance(f, ast.Name):
        return _is_jit_factory_name(f.id)
    return False


def _is_device_producing(facts, node: ast.AST, aliases: _Aliases) -> bool:
    if not isinstance(node, ast.Call):
        return False
    f = node.func
    if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
        head = f.value.id
        if head in aliases.jnp:
            return True
        if head in aliases.jax and f.attr == "device_put":
            return True
        return False
    if (
        isinstance(f, ast.Attribute)
        and isinstance(f.value, ast.Attribute)
        and isinstance(f.value.value, ast.Name)
        and f.value.value.id in aliases.jax
        and f.value.attr == "numpy"
    ):
        # the un-aliased `import jax.numpy` spelling: jax.numpy.<fn>(...)
        return True
    if isinstance(f, ast.Name):
        # a call THROUGH a jitted-program handle produces device output
        return f.id in facts.jit_handles or _is_jit_factory_name(f.id)
    return False


def _sync_call_kind(f: ast.AST, aliases: _Aliases) -> Optional[str]:
    """'np.asarray'-style label when ``f`` is a banned implicit-sync
    callable (numpy converters, scalar builtins), else None."""
    if isinstance(f, ast.Attribute) and isinstance(f.value, ast.Name):
        if f.value.id in aliases.numpy and f.attr in ("asarray", "array"):
            return f"{f.value.id}.{f.attr}"
        return None
    if isinstance(f, ast.Name) and f.id in ("float", "int", "bool"):
        return f.id
    return None


def _is_block_until_ready(f: ast.AST) -> bool:
    # jax.block_until_ready(x) and x.block_until_ready() both count
    return isinstance(f, ast.Attribute) and f.attr == "block_until_ready"


# ---------------------------------------------------------------------------
# R8: layering
# ---------------------------------------------------------------------------

# the package DAG, base to top; an import may only reach STRICTLY lower
# layers (same-package imports are free)
LAYERS: Dict[str, int] = {
    "appconsts": 0,
    "utils": 1,
    "ops": 2,
    "da": 3,
    "parallel": 4,
    "state": 5,
    "node": 6,
    "client": 7,
    "cli": 8,
    "lint": 9,
    "__init__": 10,  # the package root may touch anything (env arming)
}

_DAG_TEXT = "appconsts → utils → ops → da → parallel → state → node → client → cli"


def _layer_of(relpath: str) -> Optional[Tuple[str, int]]:
    parts = relpath.split("/")
    if len(parts) < 2 or parts[0] != "celestia_tpu":
        return None
    seg = parts[1]
    if seg.endswith(".py"):
        seg = seg[:-3]
    rank = LAYERS.get(seg)
    return (seg, rank) if rank is not None else None


@register
class LayeringRule(Rule):
    id = "layering"
    summary = "package imports follow the DAG; no back-edges, no cycles"
    doc = (
        f"Enforces {_DAG_TEXT} (lint above all): an import — module-"
        "level or lazy — from a package at the same or a higher layer is "
        "a back-edge.  The upcoming sharding refactor reworks da/state/"
        "node heavily; the DAG is what keeps 'just import it from node' "
        "from quietly inverting the architecture.  Deliberate inversions "
        "carry allow(layering) with the architectural argument."
    )

    def check(self, ctx: ModuleContext) -> Iterator[Finding]:
        me = _layer_of(ctx.relpath)
        if me is None:
            return
        my_seg, my_rank = me
        for node in ast.walk(ctx.tree):
            targets: Set[Tuple[str, int]] = set()
            if isinstance(node, ast.Import):
                for a in node.names:
                    t = _import_target(a.name)
                    if t is not None:
                        targets.add(t)
            elif isinstance(node, ast.ImportFrom):
                # resolve relative imports against this file's package
                # so `from ..node import x` can't slip under the rule
                base = _absolute_module(ctx.relpath, node.level, node.module)
                if base is not None:
                    t = _import_target(base)
                    if t is not None:
                        targets.add(t)
                    # `from celestia_tpu import node` names the package
                    # in the ALIAS, not in node.module — check each one
                    for a in node.names:
                        t = _import_target(f"{base}.{a.name}")
                        if t is not None:
                            targets.add(t)
            for seg, rank in sorted(targets):
                if seg == my_seg:
                    continue
                if rank >= my_rank:
                    yield Finding(
                        self.id, ctx.relpath, node.lineno, node.col_offset,
                        f"layering back-edge: {my_seg}/ may not import "
                        f"{seg}/ (DAG: {_DAG_TEXT})",
                    )


def _absolute_module(
    relpath: str, level: int, module: Optional[str]
) -> Optional[str]:
    """Dotted absolute module an ImportFrom refers to.  ``level`` 0 is
    already absolute; level k resolves against this file's package
    (``from ..node import x`` in state/modules/ → celestia_tpu.node)."""
    if level == 0:
        return module
    pkg_parts = relpath.split("/")[:-1]  # drop the filename
    if level > 1:
        if level - 1 > len(pkg_parts):
            return None
        pkg_parts = pkg_parts[: len(pkg_parts) - (level - 1)]
    base = ".".join(pkg_parts)
    if not base:
        return None
    return f"{base}.{module}" if module else base


def _import_target(dotted: str) -> Optional[Tuple[str, int]]:
    parts = dotted.split(".")
    if parts[0] != "celestia_tpu" or len(parts) < 2:
        return None
    rank = LAYERS.get(parts[1])
    return (parts[1], rank) if rank is not None else None
