"""Trace-safety AST lint: the static half of the program-contract gate.

The repo's correctness discipline is a set of *program contracts* —
flag-off paths lower to byte-identical HLO, host callbacks stay gated,
every counter name is documented — but contracts enforced only by
runtime byte-pin assertions fire *after* the drift shipped. This module
is the gate that fires *before*: a stdlib-``ast`` pass (deliberately
**no jax import** — the lint must run anywhere, instantly) over the
package source (``poisson_tpu/`` only) with repo-specific rules:

====================  ==================================================
rule id               contract
====================  ==================================================
``callback-gate``     host callbacks (``jax.debug.*``, ``io_callback``)
                      in fused-loop-reachable modules must sit behind a
                      static-flag ``if`` or inside a ``lax.cond`` branch
``traced-branch``     no Python ``if``/``while`` on traced values (the
                      loop-state parameter) inside a ``lax.while_loop``/
                      ``lax.cond``/``lax.scan`` body function
``static-default``    jit static-arg defaults must be hashable literals
                      (a mutable default silently splits or poisons the
                      compile cache); plain mutable defaults in solver
                      modules are flagged too
``wallclock``         no wall-clock reads (``time.time`` & friends)
                      in solver/ops/mg/integrity code — a clock in a
                      traced path is a hidden input, in host setup a
                      determinism leak
``rng``               no unseeded RNG (``random.*``,
                      ``np.random.<dist>``) in solver/ops/mg/integrity
                      code; seeded ``default_rng(<literal>)`` is fine
``counter-doc``       every ``metrics.inc``/``gauge`` string literal
                      must be documented in ``obs/metrics.py``'s
                      docstring (the metrics catalogue is the contract)
``flight-kind``       flight-recorder span/point kinds passed as string
                      literals must be declared ``SPAN_*``/``POINT_*``
                      constants in ``obs/flight.py``
``chaos-registry``    every chaos scenario function (single ``seed``
                      parameter) must be registered via ``@scenario`` so
                      it joins the ``--list`` catalogue and the campaign
``fingerprint-key``   geometry fingerprints must never reach a bucket-
                      cache or cohort key (the PR 9 co-batching
                      invariant: families share executables)
``suppression-reason``  an inline suppression without a reason string is
                      itself a finding
====================  ==================================================

Suppression syntax (requires a reason)::

    some_call()  # contracts: allow=wallclock -- host-side span timing

on the flagged line or the line directly above it. Suppressions are
kept in the report (``suppressed: true`` + the reason) so "zero
unexplained suppressions" is itself checkable.

Run via ``python -m poisson_tpu.contracts`` (with the HLO ledger and
registry drift checks) or call :func:`run_lint` directly.
"""

from __future__ import annotations

import ast
import io
import os
import re
import tokenize
from dataclasses import asdict, dataclass
from typing import Iterable, Optional

# ---------------------------------------------------------------------------
# findings and suppressions


@dataclass
class Finding:
    """One diagnostic: rule id, location, message, suppression state."""

    rule: str
    file: str
    line: int
    col: int
    message: str
    suppressed: bool = False
    reason: Optional[str] = None


_SUPPRESS_RE = re.compile(
    r"#\s*contracts:\s*allow=([A-Za-z0-9_,-]+)"
    r"(?:\s*--\s*(\S.*?))?\s*$"
)


def _parse_suppressions(source: str) -> dict:
    """line number -> (set of rule ids, reason or None). 1-based.

    Tokenized, not regexed over raw lines: the pattern inside a string
    literal or a docstring (e.g. documentation SHOWING the syntax) is
    neither a live suppression nor a reasonless-suppression finding —
    only actual ``#`` comments count."""
    out = {}
    try:
        tokens = tokenize.generate_tokens(io.StringIO(source).readline)
        for tok in tokens:
            if tok.type != tokenize.COMMENT:
                continue
            m = _SUPPRESS_RE.search(tok.string)
            if m:
                rules = {r.strip() for r in m.group(1).split(",")
                         if r.strip()}
                out[tok.start[0]] = (rules, m.group(2))
    except (tokenize.TokenError, IndentationError):
        pass  # the ast parse will surface the real syntax problem
    return out


# ---------------------------------------------------------------------------
# scope policy: which rules look where

# Modules whose code is reachable from (or traced into) the fused solve
# loops — the callback-gate / traced-branch / purity rules apply here.
_SOLVER_SCOPE = (
    "poisson_tpu/solvers/",
    "poisson_tpu/ops/",
    "poisson_tpu/mg/",
    "poisson_tpu/integrity/",
    "poisson_tpu/parallel/",
    "poisson_tpu/krylov/",
    "poisson_tpu/obs/stream.py",   # the one sanctioned callback site
)

# Purity scope (wallclock/rng): solver math modules. Exempt by path:
# the MG smoke check (a host-side harness), the watchdog (its whole
# job is wall-clock supervision of the solve from OUTSIDE the trace),
# multihost init (retry backoff timing is host-side by construction),
# and the stream sink's host half (it timestamps samples AFTER the
# gated callback has already left the device).
_PURITY_EXEMPT = ("mg/selfcheck.py", "parallel/watchdog.py",
                  "parallel/multihost.py", "obs/stream.py")

_HOST_CALLBACKS = {
    ("jax", "debug", "print"),
    ("jax", "debug", "callback"),
    ("jax", "debug", "breakpoint"),
    ("jax", "experimental", "io_callback"),
}
_HOST_CALLBACK_NAMES = {"io_callback", "pure_callback"}

_WALLCLOCK_CALLS = {
    ("time", "time"), ("time", "time_ns"), ("time", "monotonic"),
    ("time", "monotonic_ns"), ("time", "perf_counter"),
    ("time", "perf_counter_ns"), ("datetime", "now"),
    ("datetime", "utcnow"),
}

_RNG_MODULES = {"random"}          # the stdlib module
_NP_RANDOM_UNSEEDED = {
    "random", "rand", "randn", "randint", "normal", "uniform",
    "choice", "permutation", "shuffle", "seed",
}

_LOOP_COMBINATORS = {"while_loop", "cond", "scan", "fori_loop"}


def _in_scope(rel: str, scopes: Iterable[str]) -> bool:
    return any(rel.startswith(s) or rel == s.rstrip("/") for s in scopes)


def _dotted(node: ast.AST):
    """A Call's func as a dotted name tuple, or None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return tuple(reversed(parts))
    return None


# ---------------------------------------------------------------------------
# documented-name extraction (counter-doc rule)

_NAME_TOKEN = re.compile(
    r"[a-z][a-z0-9_]*(?:\.[a-z0-9_{},<>*]+)+", re.IGNORECASE)
_CODE_SPAN = re.compile(r"``([^`]+)``")


def _expand_doc_token(token: str, exact: set, prefixes: set) -> None:
    """Expand one documented token into exact names / wildcard prefixes.

    ``a.{x,y}.z`` alternates, ``a.<verdict>`` / ``a.{W}s`` wildcard the
    rest, a trailing ``.*`` is an explicit prefix wildcard.
    """
    m = re.search(r"\{([^{}]*,[^{}]*)\}", token)
    if m:
        for alt in m.group(1).split(","):
            _expand_doc_token(
                token[:m.start()] + alt.strip() + token[m.end():],
                exact, prefixes)
        return
    wild = re.search(r"[<{]", token)
    if wild:
        prefix = token[:wild.start()]
        if prefix:
            prefixes.add(prefix)
        return
    if token.endswith(".*"):
        prefixes.add(token[:-1])
        return
    exact.add(token)


def documented_metric_names(metrics_source: str) -> tuple:
    """(exact names, wildcard prefixes) documented in the
    ``obs/metrics.py`` module docstring's ````code```` spans."""
    doc = ast.get_docstring(ast.parse(metrics_source)) or ""
    exact: set = set()
    prefixes: set = set()
    for span in _CODE_SPAN.findall(doc):
        for token in _NAME_TOKEN.findall(span):
            _expand_doc_token(token, exact, prefixes)
    return exact, prefixes


def _metric_documented(name: str, exact: set, prefixes: set,
                       is_prefix: bool = False) -> bool:
    if is_prefix:
        # An f-string literal prefix: documented if any catalogued name
        # or pattern lives under it (or it lives under a pattern).
        return (any(e.startswith(name) for e in exact)
                or any(p.startswith(name) or name.startswith(p)
                       for p in prefixes))
    return name in exact or any(name.startswith(p) for p in prefixes)


def declared_flight_kinds(flight_source: str) -> set:
    """The ``SPAN_*``/``POINT_*`` string constants declared at
    ``obs/flight.py`` top level — the span/point kind taxonomy."""
    kinds = set()
    for node in ast.parse(flight_source).body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1:
            t = node.targets[0]
            if (isinstance(t, ast.Name)
                    and re.match(r"^(SPAN|POINT)_", t.id)
                    and isinstance(node.value, ast.Constant)
                    and isinstance(node.value.value, str)):
                kinds.add(node.value.value)
    return kinds


# ---------------------------------------------------------------------------
# per-file lint


class _FileLint:
    def __init__(self, rel: str, source: str, ctx: dict):
        self.rel = rel
        self.source = source
        self.ctx = ctx
        self.tree = ast.parse(source)
        self.suppressions = _parse_suppressions(source)
        self.findings: list = []
        self.parent: dict = {}
        for node in ast.walk(self.tree):
            for child in ast.iter_child_nodes(node):
                self.parent[child] = node
        # from-import bindings, so `from time import perf_counter` /
        # `from jax import debug` can't evade the module-qualified
        # rules: local name -> originating module path tuple.
        self.from_imports: dict = {}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.ImportFrom) and node.module:
                mod = tuple(node.module.split("."))
                for alias in node.names:
                    if alias.name != "*":
                        self.from_imports[alias.asname or alias.name] = \
                            mod + (alias.name,)

    # -- helpers --------------------------------------------------------

    def emit(self, rule: str, node: ast.AST, message: str) -> None:
        line = getattr(node, "lineno", 1)
        col = getattr(node, "col_offset", 0)
        f = Finding(rule=rule, file=self.rel, line=line, col=col,
                    message=message)
        for cand in (line, line - 1):
            sup = self.suppressions.get(cand)
            if sup and (rule in sup[0] or "all" in sup[0]):
                f.suppressed = True
                f.reason = sup[1]
                break
        self.findings.append(f)

    def resolve_dotted(self, node: ast.AST):
        """Like :func:`_dotted`, but with the leading name expanded
        through this file's from-import bindings — ``perf_counter()``
        after ``from time import perf_counter`` resolves to
        ``('time', 'perf_counter')``, ``debug.print(...)`` after
        ``from jax import debug`` to ``('jax', 'debug', 'print')``."""
        dotted = _dotted(node)
        if not dotted:
            return dotted
        expansion = self.from_imports.get(dotted[0])
        if expansion:
            return expansion + dotted[1:]
        return dotted

    def ancestors(self, node: ast.AST):
        cur = self.parent.get(node)
        while cur is not None:
            yield cur
            cur = self.parent.get(cur)

    def enclosing_function(self, node: ast.AST):
        for anc in self.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.Lambda)):
                return anc
        return None

    def _resolve_local_fn(self, name: str, at_line: int):
        """Nearest preceding FunctionDef with this name (loop bodies are
        local defs right above their ``lax.while_loop`` call)."""
        best = None
        for node in ast.walk(self.tree):
            if (isinstance(node, ast.FunctionDef) and node.name == name
                    and node.lineno <= at_line
                    and (best is None or node.lineno > best.lineno)):
                best = node
        return best

    # -- rules ----------------------------------------------------------

    def run(self) -> list:
        if _in_scope(self.rel, _SOLVER_SCOPE):
            self._rule_callback_gate()
            self._rule_traced_branch()
            if not any(tag in self.rel for tag in _PURITY_EXEMPT):
                self._rule_wallclock_and_rng()
            self._rule_static_default()
        self._rule_counter_doc()
        self._rule_flight_kind()
        if self.rel.endswith("testing/chaos.py"):
            self._rule_chaos_registry()
        if self.rel.endswith(("solvers/batched.py", "serve/service.py",
                              "serve/refill.py")):
            self._rule_fingerprint_key()
        self._rule_suppression_reason()
        return self.findings

    def _rule_callback_gate(self) -> None:
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = self.resolve_dotted(node.func)
            is_cb = (dotted in _HOST_CALLBACKS
                     or (dotted and len(dotted) == 1
                         and dotted[0] in _HOST_CALLBACK_NAMES)
                     or (dotted and dotted[-1] in _HOST_CALLBACK_NAMES))
            if not is_cb:
                continue
            if self._is_gated(node):
                continue
            self.emit(
                "callback-gate", node,
                f"host callback `{'.'.join(dotted)}` is reachable from "
                f"a fused-loop module without a static-flag gate — wrap "
                f"it in `if <static_flag>:` or a `lax.cond` branch so "
                f"flag-off programs stay byte-identical")

    def _is_gated(self, node: ast.Call) -> bool:
        """Gated = under a Python ``if`` (a trace-time static branch) or
        inside a function/lambda passed as a ``lax.cond`` operand."""
        for anc in self.ancestors(node):
            if isinstance(anc, ast.If):
                return True
            if isinstance(anc, (ast.Lambda, ast.FunctionDef)):
                parent = self.parent.get(anc)
                call = parent if isinstance(parent, ast.Call) else None
                if call is None:
                    # a named branch fn: check whether its *name* is
                    # passed to lax.cond anywhere in the file
                    if isinstance(anc, ast.FunctionDef):
                        for other in ast.walk(self.tree):
                            if (isinstance(other, ast.Call)
                                    and (_dotted(other.func) or ())[-1:]
                                    == ("cond",)
                                    and any(isinstance(a, ast.Name)
                                            and a.id == anc.name
                                            for a in other.args)):
                                return True
                    continue
                dotted = _dotted(call.func) or ()
                if dotted[-1:] == ("cond",):
                    return True
        return False

    def _loop_body_functions(self):
        """FunctionDefs passed (by name or inline) to lax.while_loop /
        lax.cond / lax.scan / lax.fori_loop — code that runs traced."""
        seen = set()
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = _dotted(node.func) or ()
            if not dotted or dotted[-1] not in _LOOP_COMBINATORS:
                continue
            for arg in node.args:
                if isinstance(arg, ast.Name):
                    fn = self._resolve_local_fn(arg.id, node.lineno)
                    if fn is not None and id(fn) not in seen:
                        seen.add(id(fn))
                        yield fn

    def _rule_traced_branch(self) -> None:
        for fn in self._loop_body_functions():
            params = {a.arg for a in fn.args.args}
            for node in ast.walk(fn):
                if isinstance(node, ast.While):
                    self.emit(
                        "traced-branch", node,
                        f"Python `while` inside traced loop body "
                        f"`{fn.name}` — use `lax.while_loop`; a Python "
                        f"loop here unrolls (or crashes) at trace time")
                elif isinstance(node, ast.If):
                    names = {n.id for n in ast.walk(node.test)
                             if isinstance(n, ast.Name)}
                    hit = names & params
                    if hit:
                        self.emit(
                            "traced-branch", node,
                            f"Python `if` on traced value(s) "
                            f"{sorted(hit)} inside loop body "
                            f"`{fn.name}` — branch on statics only, or "
                            f"use `lax.cond`/`jnp.where`")

    def _rule_wallclock_and_rng(self) -> None:
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = self.resolve_dotted(node.func)
            if not dotted:
                continue
            if dotted[-2:] in _WALLCLOCK_CALLS or dotted in _WALLCLOCK_CALLS:
                self.emit(
                    "wallclock", node,
                    f"wall-clock read `{'.'.join(dotted)}` in solver "
                    f"code — clocks are hidden inputs (trace-unsafe in "
                    f"a body, nondeterministic in setup); take times at "
                    f"the obs/ layer")
                continue
            is_std_rng = (len(dotted) == 2 and dotted[0] in _RNG_MODULES
                          and dotted[1] != "Random")
            is_np_rng = (len(dotted) >= 3
                         and dotted[-3:-1] in {("np", "random"),
                                               ("numpy", "random")}
                         and dotted[-1] in _NP_RANDOM_UNSEEDED)
            if is_std_rng or is_np_rng:
                self.emit(
                    "rng", node,
                    f"unseeded RNG `{'.'.join(dotted)}` in solver code "
                    f"— solver paths must be deterministic; thread a "
                    f"seeded `default_rng(seed)` from the caller")

    def _rule_static_default(self) -> None:
        for fn in ast.walk(self.tree):
            if not isinstance(fn, ast.FunctionDef):
                continue
            static_params = self._jit_static_params(fn)
            # args.defaults spans posonlyargs + args; kw-only params
            # carry their own kw_defaults list (None = no default).
            pos_params = fn.args.posonlyargs + fn.args.args
            defaults = fn.args.defaults
            defaulted = list(zip(
                pos_params[len(pos_params) - len(defaults):], defaults))
            defaulted += [(p, d) for p, d in
                          zip(fn.args.kwonlyargs, fn.args.kw_defaults)
                          if d is not None]
            for param, default in defaulted:
                bad = self._mutable_default(default)
                if bad is None:
                    continue
                if param.arg in static_params:
                    self.emit(
                        "static-default", default,
                        f"jit static arg `{param.arg}` of `{fn.name}` "
                        f"defaults to a {bad} — static args key the "
                        f"compile cache and must be hashable literals")
                else:
                    self.emit(
                        "static-default", default,
                        f"mutable default `{param.arg}={bad}` on "
                        f"`{fn.name}` — shared across calls; default "
                        f"to None and build inside")

    @staticmethod
    def _jit_static_params(fn: ast.FunctionDef) -> set:
        """Parameter names made static by @jax.jit / @functools.partial
        (jax.jit, static_argnums=/static_argnames=) decorators."""
        static: set = set()
        for dec in fn.decorator_list:
            if not isinstance(dec, ast.Call):
                continue
            dotted = _dotted(dec.func) or ()
            target_kw = dec.keywords
            if dotted[-1:] == ("partial",):
                if not any(isinstance(a, (ast.Name, ast.Attribute))
                           and (_dotted(a) or ())[-1:] == ("jit",)
                           for a in dec.args):
                    continue
            elif dotted[-1:] != ("jit",):
                continue
            for kw in target_kw:
                if kw.arg == "static_argnums":
                    try:
                        nums = ast.literal_eval(kw.value)
                    except ValueError:
                        continue
                    if isinstance(nums, int):
                        nums = (nums,)
                    positional = fn.args.posonlyargs + fn.args.args
                    for n in nums or ():
                        if 0 <= n < len(positional):
                            static.add(positional[n].arg)
                elif kw.arg == "static_argnames":
                    try:
                        names = ast.literal_eval(kw.value)
                    except ValueError:
                        continue
                    if isinstance(names, str):
                        names = (names,)
                    static.update(names or ())
        return static

    @staticmethod
    def _mutable_default(node: ast.AST):
        if isinstance(node, (ast.List, ast.Dict, ast.Set)):
            return {ast.List: "list literal", ast.Dict: "dict literal",
                    ast.Set: "set literal"}[type(node)]
        if isinstance(node, ast.Call):
            dotted = _dotted(node.func) or ("call",)
            # frozen/hashable constructors are fine
            if dotted[-1] in {"tuple", "frozenset", "MGConfig",
                              "RetryPolicy", "BreakerPolicy",
                              "DegradationPolicy", "SLOPolicy",
                              "FleetPolicy", "IntegrityPolicy",
                              "ServicePolicy"}:
                return None
            return f"call to {'.'.join(dotted)}()"
        return None

    def _rule_counter_doc(self) -> None:
        exact, prefixes = self.ctx["metric_names"]
        if self.rel.endswith("obs/metrics.py"):
            return
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            dotted = _dotted(node.func) or ()
            if dotted[-1:] not in {("inc",), ("gauge",), ("observe",)}:
                continue
            if len(dotted) >= 2 and not re.search(
                    r"(obs|metrics)", dotted[-2], re.IGNORECASE):
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                name, is_prefix = arg.value, False
            elif isinstance(arg, ast.JoinedStr):
                head = arg.values[0] if arg.values else None
                if not (isinstance(head, ast.Constant)
                        and isinstance(head.value, str)):
                    continue
                name, is_prefix = head.value, True
            else:
                continue
            if not _metric_documented(name, exact, prefixes, is_prefix):
                kind = "family prefix" if is_prefix else "name"
                self.emit(
                    "counter-doc", node,
                    f"metric {kind} `{name}` is not documented in "
                    f"obs/metrics.py — the docstring catalogue is the "
                    f"metrics contract; add it (with semantics) or "
                    f"rename onto a documented family")

    def _rule_flight_kind(self) -> None:
        kinds = self.ctx["flight_kinds"]
        if self.rel.endswith("obs/flight.py"):
            return
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call) or len(node.args) < 2:
                continue
            func = node.func
            if not (isinstance(func, ast.Attribute)
                    and func.attr in {"begin", "end", "point"}):
                continue
            recv = _dotted(func.value) or ()
            recv_txt = ".".join(recv).lower()
            if not ("flight" in recv_txt or "recorder" in recv_txt):
                continue
            arg = node.args[1]
            if (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)
                    and arg.value not in kinds):
                self.emit(
                    "flight-kind", node,
                    f"flight span/point kind '{arg.value}' is not "
                    f"declared in obs/flight.py — add a SPAN_*/POINT_* "
                    f"constant (the span taxonomy is the contract the "
                    f"trace viewer and tests validate against)")

    def _rule_chaos_registry(self) -> None:
        for node in self.tree.body:
            if not isinstance(node, ast.FunctionDef):
                continue
            args = node.args
            if (len(args.args) != 1 or args.args[0].arg != "seed"
                    or args.vararg or args.kwarg or args.kwonlyargs):
                continue
            registered = any(
                isinstance(dec, ast.Call)
                and (_dotted(dec.func) or ())[-1:] == ("scenario",)
                for dec in node.decorator_list)
            if not registered:
                self.emit(
                    "chaos-registry", node,
                    f"`{node.name}(seed)` looks like a chaos scenario "
                    f"but carries no @scenario(...) decorator — it "
                    f"would never join the --list catalogue or the "
                    f"campaign (`chaos --all` silently skips it)")

    def _rule_fingerprint_key(self) -> None:
        key_fns = {"_cohort", "_lane_cohort", "_hw_cohort",
                   "taint_compatible"}
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Assign):
                names = [t.id for t in node.targets
                         if isinstance(t, ast.Name)]
                if not any("key" in n for n in names):
                    continue
                hit = self._fingerprint_refs(node.value)
                if hit:
                    self.emit(
                        "fingerprint-key", node,
                        f"`{hit}` flows into key `{names[0]}` — "
                        f"fingerprints are operand identity, never "
                        f"executable/cohort identity (the PR 9 "
                        f"invariant: geometry families co-batch on one "
                        f"bucket executable)")
            elif (isinstance(node, ast.FunctionDef)
                  and node.name in key_fns
                  and node.name != "taint_compatible"):
                for stmt in node.body:
                    if isinstance(stmt, ast.Return) and stmt.value:
                        hit = self._fingerprint_refs(stmt.value)
                        if hit:
                            self.emit(
                                "fingerprint-key", stmt,
                                f"cohort builder `{node.name}` returns "
                                f"a value referencing `{hit}` — "
                                f"fingerprints must never split "
                                f"cohorts (families co-batch)")

    @staticmethod
    def _fingerprint_refs(node: ast.AST):
        for sub in ast.walk(node):
            if isinstance(sub, ast.Name) and (
                    "fingerprint" in sub.id or sub.id == "taint_fp"):
                return sub.id
            if isinstance(sub, ast.Attribute) and (
                    "fingerprint" in sub.attr or sub.attr == "taint_fp"):
                return sub.attr
        return None

    def _rule_suppression_reason(self) -> None:
        for line_no, (rules, reason) in self.suppressions.items():
            if reason is None or not reason.strip():
                self.findings.append(Finding(
                    rule="suppression-reason", file=self.rel,
                    line=line_no, col=0,
                    message=(
                        f"suppression for {sorted(rules)} has no reason "
                        f"string — write `# contracts: allow=<rule> -- "
                        f"<why this is safe>`"),
                ))


# ---------------------------------------------------------------------------
# tree walk + report

RULES = (
    "callback-gate", "traced-branch", "static-default", "wallclock",
    "rng", "counter-doc", "flight-kind", "chaos-registry",
    "fingerprint-key", "suppression-reason",
)

_SCAN_ROOT = "poisson_tpu"
_SKIP_PARTS = ("__pycache__",)


def _iter_sources(root: str):
    base = os.path.join(root, _SCAN_ROOT)
    for dirpath, dirnames, filenames in os.walk(base):
        dirnames[:] = [d for d in dirnames if d not in _SKIP_PARTS]
        for fname in sorted(filenames):
            if fname.endswith(".py"):
                yield os.path.join(dirpath, fname)


def _build_context(root: str) -> dict:
    def read(rel):
        try:
            with open(os.path.join(root, rel)) as f:
                return f.read()
        except OSError:
            return ""

    return {
        "metric_names": documented_metric_names(
            read("poisson_tpu/obs/metrics.py")),
        "flight_kinds": declared_flight_kinds(
            read("poisson_tpu/obs/flight.py")),
    }


def lint_source(rel: str, source: str, ctx: Optional[dict] = None) -> list:
    """Lint one source string (the unit-test seam). ``ctx`` defaults to
    empty catalogues — pass :func:`_build_context`'s output (or a
    doctored one) to exercise the catalogue-backed rules."""
    ctx = ctx or {"metric_names": (set(), set()), "flight_kinds": set()}
    return _FileLint(rel, source, ctx).run()


def run_lint(root: Optional[str] = None) -> dict:
    """Lint the tree; returns the machine-readable report dict."""
    root = os.path.abspath(root or repo_root())
    ctx = _build_context(root)
    findings: list = []
    files = 0
    for path in _iter_sources(root):
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        try:
            with open(path) as f:
                source = f.read()
        except OSError:
            continue
        try:
            findings.extend(_FileLint(rel, source, ctx).run())
        except SyntaxError as e:
            findings.append(Finding(
                rule="parse", file=rel, line=e.lineno or 1, col=0,
                message=f"source does not parse: {e.msg}"))
        files += 1
    findings.sort(key=lambda f: (f.file, f.line, f.rule))
    active = [f for f in findings if not f.suppressed]
    return {
        "schema": "poisson_tpu.contracts.lint/1",
        "root": root,
        "files": files,
        "rules": list(RULES),
        "findings": [asdict(f) for f in findings],
        "counts": {
            "findings": len(active),
            "suppressed": len(findings) - len(active),
            "rules": len(RULES),
        },
    }


def repo_root() -> str:
    """The checkout root: two levels above this file."""
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
