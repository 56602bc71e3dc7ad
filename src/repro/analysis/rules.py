"""Rule catalog + AST check functions for the repro linter.

Each rule has a stable id, a one-line summary and a fix hint; the
check functions are called by :mod:`repro.analysis.linter`'s visitor
with a per-file :class:`LintContext`.  Any finding can be silenced with
an inline escape hatch on its line::

    foo = np.random.rand(4)   # repro-lint: disable=DET001 -- justification

or for a whole file (any line)::

    # repro-lint: disable-file=PAR001 -- generated code

Rule groups:

* ``DET*`` — determinism: hidden global RNG state.
* ``HOT*`` — traced/engine hot-path hazards: host syncs, Python
  branching on traced values, registry-order-dependent iteration.
* ``PAR*`` — np ≡ jax ≡ pallas parity lanes: weak-dtype hazards.
* ``LNT*`` — the linter itself (unparseable file).

The jaxpr audit (``JXP*``), registry contracts (``CON*``) and budget
gate (``BGT*``) ids live in the same catalog so ``--list-rules`` and
the README table cover every finding the subsystem can emit.
"""
from __future__ import annotations

import ast
import dataclasses
from typing import Iterator, Optional

from .findings import Finding


@dataclasses.dataclass(frozen=True)
class Rule:
    id: str
    title: str
    hint: str
    doc: str = ""


_RULES = (
    Rule("LNT000", "file does not parse",
         "fix the syntax error; the linter skips unparseable files"),
    Rule("DET001", "unseeded numpy RNG",
         "use np.random.default_rng(seed) / np.random.Generator; the "
         "legacy global RNG (np.random.rand, .seed, ...) is hidden "
         "process state",
         "Legacy np.random.* calls share one mutable global stream: "
         "results then depend on call order across the whole process, "
         "which breaks replayable experiments."),
    Rule("DET002", "unseeded Python random",
         "use a seeded np.random.default_rng(seed) (or random.Random("
         "seed)) instead of the random module's global instance"),
    Rule("HOT001", "host sync inside traced function",
         "keep values on device: drop float()/int()/.item()/np.asarray "
         "from jitted code; use jnp ops (a traced value cannot be "
         "concretized without blocking the trace)"),
    Rule("HOT002", "Python branch on traced value",
         "use jnp.where / lax.cond / lax.select — a Python if on a "
         "traced value either fails to trace or silently bakes in one "
         "branch at trace time"),
    Rule("HOT003", "registry dict iteration in engine hot path",
         "iterate a sorted(...) snapshot (or resolve entries up front); "
         "raw registry iteration order depends on registration order"),
    Rule("PAR001", "weak-dtype array creation in parity lane",
         "pass an explicit dtype= (e.g. jnp.zeros(shape, "
         "dtype=jnp.float64)); weak-typed arrays let XLA re-promote "
         "differently from the numpy oracle"),
    Rule("PAR002", "builtin-type astype in parity lane",
         "astype(float) resolves to the platform default dtype; pin "
         "jnp.float64 / np.float64 explicitly"),
    Rule("JXP001", "weak-typed engine output or scan carry",
         "pin the dtype where the buffer is created; weak carries "
         "re-promote on the next op and can recompile per call site"),
    Rule("JXP002", "scan/while carry structure or dtype drift",
         "make the carry pytree structure and leaf dtypes identical "
         "between iterations (initialize with the final dtypes)"),
    Rule("JXP003", "unexpected 64-bit value in audited program",
         "this lane is declared 32-bit; find the promoting op "
         "(Python float literals and np scalars promote) and pin dtypes"),
    Rule("JXP004", "host callback inside compiled engine",
         "remove debug prints / pure_callback from the hot path, or "
         "gate them out of production engines"),
    Rule("JXP005", "engine cache key misses a config field",
         "add the field to repro.core.simulator._cache_key — two "
         "configs differing in it would silently share a compiled "
         "engine"),
    Rule("JXP006", "64-bit integer div/rem inside a scan/while body",
         "compute the index in int32 (e.g. lax.argmin(x, 0, jnp.int32)); "
         "the TPU has no 64-bit integer unit and emulates each 64-bit "
         "division as a long dependent chain, paid every iteration"),
    Rule("CON001", "balancer registry contract violation",
         "declared backends must be callable factories; stateful "
         "balancers (init_state set) must return (select, on_complete) "
         "pairs from every backend factory"),
    Rule("CON002", "sched registry contract violation",
         "a registered sched needs callable make_np and make_jax "
         "factories (both engines resolve it)"),
    Rule("CON003", "keep-alive registry contract violation",
         "factories must return (windows, observe); stateful policies "
         "need init_state and a non-None observe on every backend"),
    Rule("CON004", "kernel package contract violation",
         "a kernel package ships kernel.py + ops.py + ref.py with a "
         "<op>_ref reference matching the op's signature"),
    Rule("BGT001", "jaxpr eqn budget exceeded",
         "the engine's traced program grew past its recorded budget — "
         "a fusion break or accidental unrolling; inspect "
         "jax.make_jaxpr of the engine and re-baseline deliberately if "
         "intended"),
)

RULES: dict[str, Rule] = {r.id: r for r in _RULES}

#: Rules emitted by the AST linter (the rest come from the jaxpr audit,
#: contract checks and budget gate).
LINT_RULE_IDS = ("DET001", "DET002", "HOT001", "HOT002", "HOT003",
                 "PAR001", "PAR002")


@dataclasses.dataclass
class LintContext:
    """Per-file state the check functions read (linter.py maintains it)."""

    path: str                       # as reported in findings
    np_aliases: set[str]            # names bound to the numpy module
    jnp_aliases: set[str]           # names bound to jax.numpy
    random_aliases: set[str]        # names bound to stdlib random
    is_hot_path: bool
    is_parity: bool
    traced_depth: int = 0           # >0 inside a traced function
    traced_params: Optional[set] = None   # union of traced fns' params

    @property
    def in_traced(self) -> bool:
        return self.traced_depth > 0


def _finding(ctx: LintContext, node: ast.AST, rule_id: str,
             message: str) -> Finding:
    return Finding(path=ctx.path, line=getattr(node, "lineno", 0),
                   rule=rule_id, message=message,
                   hint=RULES[rule_id].hint)


def _dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for an Attribute/Name chain, else None."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


# Legacy global-stream numpy RNG entry points (module-level functions of
# np.random that mutate the hidden global RandomState).
LEGACY_NP_RANDOM = frozenset({
    "seed", "rand", "randn", "randint", "random", "random_sample",
    "ranf", "sample", "choice", "shuffle", "permutation", "uniform",
    "normal", "standard_normal", "exponential", "poisson", "binomial",
    "beta", "gamma", "lognormal", "pareto", "weibull", "zipf",
    "get_state", "set_state", "random_integers", "bytes",
})

# Stdlib random module functions backed by its hidden global instance.
GLOBAL_PY_RANDOM = frozenset({
    "seed", "random", "randint", "randrange", "choice", "choices",
    "shuffle", "sample", "uniform", "triangular", "betavariate",
    "expovariate", "gammavariate", "gauss", "lognormvariate",
    "normalvariate", "vonmisesvariate", "paretovariate",
    "weibullvariate", "getrandbits", "randbytes",
})

# Weak-dtype jnp constructors and the argument position after which a
# positional dtype may appear (zeros(shape, dtype), full(shape, v, dtype)).
_WEAK_CTORS = {"zeros": 2, "ones": 2, "empty": 2, "full": 3,
               "arange": 4, "linspace": 3}

_HOST_SYNC_ATTRS = frozenset({"item", "tolist"})
_HOST_SYNC_NP = frozenset({"asarray", "array", "copyto"})


def check_import(node: ast.AST, ctx: LintContext) -> Iterator[Finding]:
    """``from random import shuffle`` pulls in the global instance."""
    if isinstance(node, ast.ImportFrom) and node.module == "random" \
            and node.level == 0:
        pulled = [a.name for a in node.names
                  if a.name in GLOBAL_PY_RANDOM or a.name == "*"]
        if pulled:
            yield _finding(
                ctx, node, "DET002",
                f"from random import {', '.join(pulled)} binds the "
                f"module's hidden global RNG instance")


def check_call(node: ast.Call, ctx: LintContext) -> Iterator[Finding]:
    dotted = _dotted(node.func)

    # --- DET001: legacy numpy global RNG -----------------------------
    if dotted:
        head, _, rest = dotted.partition(".")
        if head in ctx.np_aliases and rest.startswith("random."):
            fn = rest[len("random."):]
            if fn in LEGACY_NP_RANDOM:
                yield _finding(
                    ctx, node, "DET001",
                    f"np.random.{fn} uses the hidden global RandomState")
            elif fn in ("default_rng", "RandomState") and not node.args \
                    and not node.keywords:
                yield _finding(
                    ctx, node, "DET001",
                    f"np.random.{fn}() without a seed is "
                    f"entropy-seeded (non-reproducible)")

        # --- DET002: stdlib random global instance -------------------
        if head in ctx.random_aliases:
            if rest in GLOBAL_PY_RANDOM:
                yield _finding(
                    ctx, node, "DET002",
                    f"random.{rest} uses the module's hidden global "
                    f"RNG instance")
            elif rest == "Random" and not node.args and not node.keywords:
                yield _finding(ctx, node, "DET002",
                               "random.Random() without a seed is "
                               "entropy-seeded (non-reproducible)")

    # --- HOT001: host syncs inside traced code -----------------------
    if ctx.in_traced:
        if isinstance(node.func, ast.Name) \
                and node.func.id in ("float", "int", "bool") \
                and node.args \
                and not isinstance(node.args[0], ast.Constant):
            yield _finding(
                ctx, node, "HOT001",
                f"{node.func.id}(...) concretizes a traced value "
                f"(host sync / ConcretizationTypeError)")
        elif isinstance(node.func, ast.Attribute) \
                and node.func.attr in _HOST_SYNC_ATTRS:
            yield _finding(
                ctx, node, "HOT001",
                f".{node.func.attr}() pulls a traced value to host")
        elif dotted:
            head, _, rest = dotted.partition(".")
            if head in ctx.np_aliases and rest in _HOST_SYNC_NP:
                yield _finding(
                    ctx, node, "HOT001",
                    f"np.{rest} materializes a traced value on host "
                    f"(numpy call inside a jax trace)")

    # --- PAR001 / PAR002: weak dtypes in parity lanes ----------------
    if ctx.is_parity and dotted:
        head, _, rest = dotted.partition(".")
        if head in ctx.jnp_aliases and rest in _WEAK_CTORS:
            has_dtype = any(kw.arg == "dtype" for kw in node.keywords) \
                or len(node.args) >= _WEAK_CTORS[rest]
            if not has_dtype:
                yield _finding(
                    ctx, node, "PAR001",
                    f"jnp.{rest} without an explicit dtype creates a "
                    f"weak/default-typed array")
    if ctx.is_parity and isinstance(node.func, ast.Attribute) \
            and node.func.attr == "astype" and len(node.args) == 1 \
            and isinstance(node.args[0], ast.Name) \
            and node.args[0].id in ("float", "int", "bool", "complex"):
        yield _finding(
            ctx, node, "PAR002",
            f"astype({node.args[0].id}) resolves to the platform "
            f"default dtype")


def _names_in(node: ast.AST) -> set[str]:
    return {n.id for n in ast.walk(node) if isinstance(n, ast.Name)}


def check_branch(node: ast.AST, ctx: LintContext) -> Iterator[Finding]:
    """HOT002: Python control flow on a traced value."""
    if not ctx.in_traced or not ctx.traced_params:
        return
    test = getattr(node, "test", None)
    if test is None:
        return
    hits = _names_in(test) & ctx.traced_params
    if hits:
        kind = {ast.If: "if", ast.While: "while",
                ast.IfExp: "conditional expression",
                ast.Assert: "assert"}.get(type(node), "branch")
        yield _finding(
            ctx, node, "HOT002",
            f"Python {kind} on traced value(s) "
            f"{', '.join(sorted(hits))}")


def check_iteration(node: ast.AST, ctx: LintContext) -> Iterator[Finding]:
    """HOT003: raw registry-dict iteration in a hot-path module."""
    from .registry import REGISTRY_NAMES
    if not ctx.is_hot_path:
        return
    iters: list[ast.AST] = []
    if isinstance(node, (ast.For, ast.comprehension)):
        iters.append(node.iter)
    for it in iters:
        target = it
        # unwrap REG.items() / .keys() / .values()
        if isinstance(it, ast.Call) and isinstance(it.func, ast.Attribute) \
                and it.func.attr in ("items", "keys", "values"):
            target = it.func.value
        name = target.id if isinstance(target, ast.Name) else \
            (_dotted(target) or "").rsplit(".", 1)[-1]
        if name in REGISTRY_NAMES:
            yield _finding(
                ctx, node, "HOT003",
                f"iteration over open registry {name} in an engine hot "
                f"path (order = registration order)")
