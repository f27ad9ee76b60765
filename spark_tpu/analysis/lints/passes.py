"""Built-in lint passes (see package docstring for the catalog)."""

from __future__ import annotations

import ast
import re
from typing import List, Tuple

from . import LintContext, LintPass, register_lint

# ---------------------------------------------------------------------------
# metric-prefix
# ---------------------------------------------------------------------------


def _metric_prefix_of(node: ast.expr):
    """(kind, literal-or-None) for an add_metric name argument."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return "literal", node.value
    if isinstance(node, ast.JoinedStr):
        if node.values and isinstance(node.values[0], ast.Constant) \
                and isinstance(node.values[0].value, str) \
                and node.values[0].value:
            return "fstring", node.values[0].value
        return "fstring", None
    return "dynamic", None


@register_lint
class MetricPrefixPass(LintPass):
    """Every `ctx.add_metric(...)` name must use a registered prefix
    (observability.metrics.METRIC_PREFIXES): an unregistered traced
    metric would flow into the event log but silently miss every
    history summary column."""

    name = "metric-prefix"
    code = "MP100"
    doc = "add_metric names use registered METRIC_PREFIXES prefixes"

    def check(self, tree, relpath, ctx: LintContext
              ) -> List[Tuple[int, str]]:
        problems = []
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "add_metric"
                    and node.args):
                continue
            kind, text = _metric_prefix_of(node.args[0])
            if text is None:
                problems.append(
                    (node.lineno,
                     f"metric name not statically attributable "
                     f"({kind} argument)"))
            elif not text.startswith(ctx.metric_prefixes):
                problems.append(
                    (node.lineno,
                     f"unregistered metric prefix: {text!r}"))
        return problems


# ---------------------------------------------------------------------------
# conf-key
# ---------------------------------------------------------------------------

#: what a conf key looks like (dots, camelCase segments)
_KEY_RX = re.compile(r"^spark_tpu(\.[A-Za-z][A-Za-z0-9]*)+$")

#: Conf methods whose first argument is a key
_CONF_METHODS = ("get", "set", "contains", "unset", "is_explicitly_set")


@register_lint
class ConfKeyPass(LintPass):
    """Every `spark_tpu.*` key string read/written through a Conf
    method — or bound to a `*_KEY` module constant — must be
    `register()`ed in config.py. A typo'd key never errors: `get`
    silently returns the fallback and the feature quietly disables
    (the PR-2 `stage_rnu` shape, for configuration)."""

    name = "conf-key"
    code = "CK100"
    doc = "conf-key string literals are registered in config.py"

    def scope(self, relpath: str) -> bool:
        if relpath == "spark_tpu/config.py":
            return False  # register() calls DEFINE the keys
        return relpath.startswith(("spark_tpu/", "tests/", "scripts/"))

    def check(self, tree, relpath, ctx: LintContext
              ) -> List[Tuple[int, str]]:
        problems = []

        def check_key(lineno: int, key: str, via: str) -> None:
            if key not in ctx.conf_keys:
                problems.append(
                    (lineno,
                     f"unregistered conf key {key!r} ({via}); add a "
                     f"register(...) entry in spark_tpu/config.py"))

        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _CONF_METHODS and node.args:
                a = node.args[0]
                if isinstance(a, ast.Constant) \
                        and isinstance(a.value, str) \
                        and a.value.startswith("spark_tpu."):
                    check_key(a.lineno, a.value,
                              f"conf.{node.func.attr}")
            elif isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Constant) \
                    and isinstance(node.value.value, str) \
                    and _KEY_RX.match(node.value.value):
                names = [t.id for t in node.targets
                         if isinstance(t, ast.Name)]
                if any(n.endswith("_KEY") for n in names):
                    check_key(node.lineno, node.value.value,
                              f"{names[0]} constant")
        return problems


# ---------------------------------------------------------------------------
# fault-site
# ---------------------------------------------------------------------------

FAULTS_MODULE = "spark_tpu/testing/faults.py"


@register_lint
class FaultSitePass(LintPass):
    """Three-way consistency for fault-injection sites: `faults.fire`
    seams <-> `testing.faults.KNOWN_SITES` <-> inject-rule string
    literals in tests/scripts. A rule naming an unwired site would arm
    and then never fire — the chaos test silently tests nothing."""

    name = "fault-site"
    code = "FS100"
    doc = "fault sites are declared, wired, and spelled consistently"

    def __init__(self):
        self._engine_wired: dict = {}  # site -> first (relpath, line)
        self._registered: set = set()  # register_site("...") literals
        self._uses: list = []  # (relpath, line, site, via)

    def scope(self, relpath: str) -> bool:
        return relpath.startswith(("spark_tpu/", "tests/", "scripts/"))

    def _spec_rules(self, text: str, ctx: LintContext):
        """Parse `text` as an inject spec; None unless EVERY comma part
        matches `site:fault:nth[:arg]` with a known fault class (the
        disambiguator against arbitrary colon-bearing strings)."""
        rules = []
        parts = [p for p in text.split(",") if p.strip()]
        if not parts:
            return None
        for part in parts:
            bits = part.strip().split(":")
            if len(bits) not in (3, 4) or any(" " in b for b in bits):
                return None
            if not re.match(r"^[a-z_][a-z0-9_]*$", bits[0]):
                return None  # f-string fragments etc. — not a spec
            if bits[1] not in ctx.fault_classes:
                return None
            if not bits[2].isdigit():
                return None
            rules.append(bits[0])
        return rules

    def check(self, tree, relpath, ctx: LintContext
              ) -> List[Tuple[int, str]]:
        # collect only; every verdict lands in finish(), so the pass is
        # independent of file-walk order (a test may register_site a
        # seam the same file then uses)
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and node.args \
                    and relpath != FAULTS_MODULE:
                fn = node.func
                callee = fn.attr if isinstance(fn, ast.Attribute) else (
                    fn.id if isinstance(fn, ast.Name) else None)
                a = node.args[0]
                lit = a.value if (isinstance(a, ast.Constant)
                                  and isinstance(a.value, str)) else None
                if callee == "fire" and lit is not None:
                    if relpath.startswith("spark_tpu/"):
                        self._engine_wired.setdefault(
                            lit, (relpath, a.lineno))
                    self._uses.append((relpath, a.lineno, lit, "fire"))
                elif callee in ("register_site", "scoped_site") \
                        and lit is not None:
                    self._registered.add(lit)
            elif isinstance(node, ast.Constant) \
                    and isinstance(node.value, str):
                for site in self._spec_rules(node.value, ctx) or ():
                    self._uses.append((relpath, node.lineno, site,
                                       "inject rule"))
        return []

    def finish(self, ctx: LintContext):
        known = set(ctx.fault_sites) | self._registered
        out = []
        seen = set()
        for relpath, line, site, via in self._uses:
            if site in known or (relpath, line, site) in seen:
                continue
            seen.add((relpath, line, site))
            out.append((relpath, line,
                        f"{via} names unknown fault site {site!r} "
                        f"(not in KNOWN_SITES, never register_site'd); "
                        f"known: {ctx.fault_sites}"))
        for site in ctx.fault_sites:
            if site not in self._engine_wired:
                out.append((FAULTS_MODULE, 1,
                            f"KNOWN_SITES declares {site!r} but no "
                            f"faults.fire({site!r}) seam wires it"))
        return out


# ---------------------------------------------------------------------------
# readme-metrics
# ---------------------------------------------------------------------------


@register_lint
class ReadmeMetricsPass(LintPass):
    """Every registered METRIC_PREFIXES entry must appear in the README
    metric-name reference table: a prefix the docs don't list is a
    metric family operators can't discover (the README table is the
    operator-facing half of the registration discipline the
    metric-prefix pass enforces in code)."""

    name = "readme-metrics"
    code = "RM100"
    doc = "every METRIC_PREFIXES entry appears in the README table"

    def scope(self, relpath: str) -> bool:
        return False  # whole-tree pass: finish() reads README.md

    def check(self, tree, relpath, ctx: LintContext):
        return []

    def finish(self, ctx: LintContext):
        import os
        path = os.path.join(ctx.repo, "README.md")
        try:
            with open(path) as f:
                text = f.read()
        except OSError:
            return [("README.md", 1, "README.md unreadable")]
        out = []
        for prefix in ctx.metric_prefixes:
            if f"`{prefix}" not in text:
                out.append(
                    ("README.md", 1,
                     f"metric prefix `{prefix}` (METRIC_PREFIXES) is "
                     f"missing from the README metric-name reference "
                     f"table"))
        return out


# ---------------------------------------------------------------------------
# rule-registry
# ---------------------------------------------------------------------------


@register_lint
class RuleRegistryPass(LintPass):
    """Optimizer-rule registration discipline, enforced at the class
    level: every `Rule` subclass in the engine (1) carries a unique
    `name` (rule traces, `excludedRules` ablation and
    `PlanIntegrityError` attribution all key on it), (2) is reachable
    from `default_optimizer()` (an orphaned rule is dead code the
    fuzzer can never ablate), and (3) declares `schema_preserving`
    explicitly in its own body — the plan-integrity verifier holds
    undeclared rules to the preservation contract, so an implicit
    inheritance is a latent false positive/negative."""

    name = "rule-registry"
    code = "RL100"
    doc = "Rule subclasses: unique name, reachable, explicit " \
          "schema_preserving"

    def scope(self, relpath: str) -> bool:
        return False  # whole-tree pass: finish() imports the registry

    def check(self, tree, relpath, ctx: LintContext):
        return []

    def _subclasses(self, base) -> list:
        out = []
        for cls in base.__subclasses__():
            out.append(cls)
            out.extend(self._subclasses(cls))
        return out

    def finish(self, ctx: LintContext):
        import inspect
        import os
        from ...plan import join_reorder  # noqa: F401 — registers rules
        from ...plan import optimizer
        from ...plan.rules import Rule

        def site(cls) -> Tuple[str, int]:
            try:
                relpath = os.path.relpath(inspect.getsourcefile(cls),
                                          ctx.repo)
                line = inspect.getsourcelines(cls)[1]
            except (OSError, TypeError):
                relpath, line = "spark_tpu/plan/rules.py", 1
            return relpath, line

        engine_rules = [cls for cls in self._subclasses(Rule)
                        if cls.__module__.startswith("spark_tpu.")]
        reachable = {type(r)
                     for b in optimizer.default_optimizer().batches
                     for r in b.rules}
        out = []
        by_name: dict = {}
        for cls in engine_rules:
            relpath, line = site(cls)
            rname = cls.__dict__.get("name")
            if not rname:
                out.append((relpath, line,
                            f"Rule subclass {cls.__name__} has no "
                            f"`name` of its own (traces/ablation/"
                            f"integrity errors key on it)"))
            elif rname in by_name:
                out.append((relpath, line,
                            f"duplicate rule name {rname!r} (also "
                            f"{by_name[rname].__name__}): excludedRules "
                            f"and rule traces cannot distinguish them"))
            else:
                by_name[rname] = cls
            if cls not in reachable:
                out.append((relpath, line,
                            f"rule {cls.__name__} is not reachable "
                            f"from default_optimizer(): dead rule the "
                            f"fuzzer can never ablate"))
            if not isinstance(cls.__dict__.get("schema_preserving"),
                              bool):
                out.append((relpath, line,
                            f"rule {cls.__name__} does not declare "
                            f"`schema_preserving` in its own body; the "
                            f"plan-integrity verifier needs the "
                            f"explicit contract (True = must preserve "
                            f"the root schema, False = legitimately "
                            f"reshapes)"))
        ctx.notes.append(
            f"rule-registry: {len(engine_rules)} engine rule(s), "
            f"{len(reachable)} reachable from default_optimizer")
        return out


# ---------------------------------------------------------------------------
# tracer-leak
# ---------------------------------------------------------------------------

#: names/attributes whose presence marks an expression as (potentially)
#: traced device data
_TRACED_NAMES = ("jnp", "lax")
_TRACED_ATTRS = ("data", "validity", "elem_validity", "selection")


def _mentions_traced(node: ast.expr) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Name) and sub.id in _TRACED_NAMES:
            return True
        if isinstance(sub, ast.Attribute) and sub.attr in _TRACED_ATTRS:
            return True
    return False


@register_lint
class TracerLeakPass(LintPass):
    """The PR-1 `_dict_value_hashes` bug class: `hash()` of a traced
    value (or truthiness coercion of device data) inside the trace-time
    modules produces trace-order-dependent identities — dict/set keying
    on them silently misbehaves across retraces. Flag the shapes
    statically in the trace-adjacent packages: execution/ + parallel/
    (the original scope), plus service/, streaming.py and
    observability/ — all of which hold device values since the
    PR-6/8/11 concurrency work (the scope predates them)."""

    name = "tracer-leak"
    code = "TL100"
    doc = "no hash()/bool() of traced values in trace-time modules"

    def scope(self, relpath: str) -> bool:
        return relpath.startswith(("spark_tpu/execution/",
                                   "spark_tpu/parallel/",
                                   "spark_tpu/service/",
                                   "spark_tpu/observability/")) \
            or relpath == "spark_tpu/streaming.py"

    def check(self, tree, relpath, ctx: LintContext
              ) -> List[Tuple[int, str]]:
        problems = []
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)):
                continue
            if node.func.id == "hash" and node.args:
                if not all(isinstance(a, ast.Constant)
                           for a in node.args):
                    problems.append(
                        (node.lineno,
                         "hash() of a non-constant in a trace-time "
                         "module: a traced value here yields a "
                         "trace-order-dependent identity (use a "
                         "structural key instead)"))
            elif node.func.id == "bool" and node.args \
                    and _mentions_traced(node.args[0]):
                problems.append(
                    (node.lineno,
                     "bool() over device data in a trace-time module: "
                     "coercing a tracer raises (or silently "
                     "host-syncs a concrete array)"))
        return problems
