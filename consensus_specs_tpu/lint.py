"""Static checks over the built specs — the role of the reference's
mypy/pylint pass (`/root/reference/Makefile:183-189`), adapted to the
flat exec'd-namespace architecture where import-based type checkers
cannot resolve names.

Three checks per fork x preset:

1. **Undefined names**: every `Name` load inside every spec function
   must resolve in the built namespace, builtins, or a local binding.
   This statically catches the NameError class of spec bug (a call to a
   helper that no fork in the chain defines).  Lambdas get their own
   scope: their parameters neither leak into the enclosing function's
   bound set nor go unchecked inside the lambda body.
2. **config-attribute discipline**: every `config.X` attribute access
   must exist in the loaded Configuration for that preset.
3. **call arity**: every call from a LIVE spec function (one whose
   definition survived fork overriding into the built namespace) to a
   spec-defined helper must bind against the helper's signature in
   that namespace — the fork-override drift the undefined-name check
   cannot see (the name exists; its parameters changed).

Plus one repo-wide check:

3. **env-knob discipline**: every `os.environ` read of a `CST_*`
   variable anywhere in the tree must have a row in README.md's
   "Environment knobs" table (and every table row must still have a
   read) — the knob surface cannot silently drift from its docs.

Run via `python -m consensus_specs_tpu.lint` (wired into `make lint`).
"""

from __future__ import annotations

import ast
import builtins
import inspect
import re
import sys
import types
from pathlib import Path

from .models.builder import (
    BUILDABLE_FORKS,
    PKG_ROOT,
    SPEC_SOURCES,
    build_spec,
    fork_chain,
)


class _LocalBindings(ast.NodeVisitor):
    """Names bound inside one function scope (params, assignments,
    targets, comprehensions, nested defs, imports, exception aliases)."""

    def __init__(self):
        self.bound: set[str] = set()

    def _bind_target(self, node):
        for child in ast.walk(node):
            if isinstance(child, ast.Name) and isinstance(
                    child.ctx, (ast.Store, ast.Del)):
                self.bound.add(child.id)

    def visit_arguments(self, node):
        for arg in (list(node.posonlyargs) + list(node.args)
                    + list(node.kwonlyargs)):
            self.bound.add(arg.arg)
        if node.vararg:
            self.bound.add(node.vararg.arg)
        if node.kwarg:
            self.bound.add(node.kwarg.arg)
        self.generic_visit(node)

    def visit_Assign(self, node):
        for target in node.targets:
            self._bind_target(target)
        self.generic_visit(node)

    def visit_AnnAssign(self, node):
        self._bind_target(node.target)
        self.generic_visit(node)

    def visit_AugAssign(self, node):
        self._bind_target(node.target)
        self.generic_visit(node)

    def visit_For(self, node):
        self._bind_target(node.target)
        self.generic_visit(node)

    def visit_withitem(self, node):
        if node.optional_vars is not None:
            self._bind_target(node.optional_vars)
        self.generic_visit(node)

    def visit_comprehension(self, node):
        self._bind_target(node.target)
        self.generic_visit(node)

    def visit_NamedExpr(self, node):
        self._bind_target(node.target)
        self.generic_visit(node)

    def visit_ExceptHandler(self, node):
        if node.name:
            self.bound.add(node.name)
        self.generic_visit(node)

    def visit_FunctionDef(self, node):
        self.bound.add(node.name)
        self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node):
        # Lambdas are their OWN scope: binding their parameters here
        # would leak them into the enclosing function's bound set and
        # mask genuine undefined-name findings after the lambda (the
        # body is checked separately by `_scope_findings`).  Only the
        # default expressions evaluate in the enclosing scope.
        for default in list(node.args.defaults) + [
                d for d in node.args.kw_defaults if d is not None]:
            self.visit(default)

    def visit_ClassDef(self, node):
        self.bound.add(node.name)
        self.generic_visit(node)

    def visit_Import(self, node):
        for alias in node.names:
            self.bound.add((alias.asname or alias.name).split(".")[0])

    visit_ImportFrom = visit_Import

    def visit_Global(self, node):
        self.bound.update(node.names)

    visit_Nonlocal = visit_Global


def _split_lambdas(root):
    """Walk `root` like ast.walk but stop at every Lambda subtree,
    returning (nodes_in_this_scope, lambdas_found).  Callers recurse on
    each lambda's body with its parameters bound (a body that is itself
    a lambda lands in `lambdas` again, so chains nest correctly)."""
    nodes, lambdas = [], []
    stack = [root]
    while stack:
        node = stack.pop()
        if isinstance(node, ast.Lambda):
            lambdas.append(node)
            continue
        nodes.append(node)
        stack.extend(ast.iter_child_nodes(node))
    return nodes, lambdas


def _scope_findings(root, bound: set[str], config_keys: set[str],
                    path: str, owner: str):
    """Name/config findings for one scope; lambda subtrees recurse with
    their parameters (and walrus bindings) added to the bound set."""
    nodes, lambdas = _split_lambdas(root)
    findings = []
    for node in nodes:
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            if node.id not in bound:
                findings.append(
                    f"{path}:{node.lineno}: undefined name "
                    f"'{node.id}' in {owner}()")
        elif (isinstance(node, ast.Attribute)
              and isinstance(node.value, ast.Name)
              and node.value.id == "config"
              and isinstance(node.ctx, ast.Load)):
            if node.attr not in config_keys:
                findings.append(
                    f"{path}:{node.lineno}: unknown config attribute "
                    f"'config.{node.attr}' in {owner}()")
    for lam in lambdas:
        lam_locals = _LocalBindings()
        lam_locals.visit_arguments(lam.args)
        lam_locals.visit(lam.body)           # walrus bindings in the body
        findings.extend(_scope_findings(
            lam.body, bound | lam_locals.bound, config_keys, path, owner))
        # default expressions evaluate in the ENCLOSING scope
        for default in list(lam.args.defaults) + [
                d for d in lam.args.kw_defaults if d is not None]:
            findings.extend(_scope_findings(
                default, bound, config_keys, path, owner))
    return findings


def _function_findings(fn_node, known: set[str], config_keys: set[str],
                       path: str):
    locals_visitor = _LocalBindings()
    locals_visitor.visit(fn_node)
    bound = locals_visitor.bound | known
    return _scope_findings(fn_node, bound, config_keys, path,
                           fn_node.name)


def _call_arity_findings(fn_node, spec_funcs: dict, sig_cache: dict,
                         path: str):
    """Calls to spec-defined helpers must bind against the callee's
    signature in the BUILT namespace (catches fork-override parameter
    drift).  Skips *args/**kwargs call sites and locally shadowed
    names; placeholder binding checks arity/keywords only."""
    locals_visitor = _LocalBindings()
    locals_visitor.visit(fn_node)
    shadowed = locals_visitor.bound

    findings = []
    for node in ast.walk(fn_node):
        if not isinstance(node, ast.Call) \
                or not isinstance(node.func, ast.Name):
            continue
        name = node.func.id
        if name in shadowed or name not in spec_funcs:
            continue
        if any(isinstance(a, ast.Starred) for a in node.args) \
                or any(kw.arg is None for kw in node.keywords):
            continue
        if name not in sig_cache:
            try:
                sig_cache[name] = inspect.signature(spec_funcs[name])
            except (TypeError, ValueError):
                sig_cache[name] = None
        sig = sig_cache[name]
        if sig is None:
            continue
        try:
            sig.bind(*([None] * len(node.args)),
                     **{kw.arg: None for kw in node.keywords})
        except TypeError as exc:
            findings.append(
                f"{path}:{node.lineno}: call to {name}() in "
                f"{fn_node.name}() does not match the spec signature "
                f"{sig}: {exc}")
    return findings


def _is_live_def(node: ast.FunctionDef, path, spec) -> bool:
    """Did this source definition survive fork overriding into the
    built namespace?  Superseded bodies never run, so arity-checking
    them against the final namespace would be noise.

    The namespace entry may be the builder's LRU cache wrapper
    (`_install_caches` rewraps get_beacon_committee & co.) — unwrap
    through `__wrapped__` before comparing code locations, else those
    helpers' own bodies would silently escape the arity check.  A
    decorated def's co_firstlineno is its first decorator line, so any
    of those lines counts as a match."""
    obj = spec._namespace.get(node.name)
    if obj is None:
        return False
    try:
        obj = inspect.unwrap(obj)
    except ValueError:          # wrapper cycle — never ours
        return False
    code = getattr(obj, "__code__", None)
    def_lines = {node.lineno} | {d.lineno for d in node.decorator_list}
    return (code is not None and code.co_filename == str(path)
            and code.co_firstlineno in def_lines)


def lint_spec(fork: str, preset: str) -> list[str]:
    spec = build_spec(fork, preset)
    known = set(spec._namespace) | set(vars(builtins))
    config_keys = set(spec.config.to_dict())
    spec_funcs = {name: obj for name, obj in spec._namespace.items()
                  if isinstance(obj, types.FunctionType)}
    sig_cache: dict = {}

    findings = []
    for chain_fork in fork_chain(fork):
        for source in SPEC_SOURCES[chain_fork]:
            path = PKG_ROOT / "models" / chain_fork / source
            tree = ast.parse(path.read_text())
            rel = str(path.relative_to(PKG_ROOT.parent))
            # top-level functions and methods only: nested defs are
            # checked inside their parent's scope walk.  Call arity is
            # checked for LIVE top-level defs only (methods are called
            # through instances, not the flat namespace).
            for node in tree.body:
                if isinstance(node, ast.ClassDef):
                    for sub in node.body:
                        if isinstance(sub, (ast.FunctionDef,
                                            ast.AsyncFunctionDef)):
                            findings.extend(_function_findings(
                                sub, known, config_keys, rel))
                elif isinstance(node, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    findings.extend(_function_findings(
                        node, known, config_keys, rel))
                    if _is_live_def(node, path, spec):
                        findings.extend(_call_arity_findings(
                            node, spec_funcs, sig_cache, rel))
    return findings


# any os.environ get/subscript/setdefault or os.getenv whose string key
# carries the CST_ prefix, matched against whole-file text so reads
# wrapped across lines still register.  Internal knobs (leading
# underscore, e.g. _CST_DRYRUN_SUBPROCESS) are exempt by the prefix
# anchor.
_ENV_READ_RE = re.compile(
    r"""(?:environ(?:\.get|\.setdefault)?\s*[\(\[]|getenv\s*\()"""
    r"""\s*['"](CST_[A-Z0-9_]+)""")
_SKIP_DIRS = {"__pycache__", ".git", ".jax_cache", ".pytest_cache",
              "out", ".claude", ".venv", "venv", ".eggs",
              "site-packages", "node_modules"}


def lint_env_knobs(repo=None) -> list[str]:
    """Every `CST_*` env read in the tree needs a row in README.md's
    knob table, and every row needs a surviving read.  Serving knobs
    (`CST_SERVE_*`) additionally need a mention in the README's
    "Serving" section, incremental-merkleization knobs
    (`CST_MERKLE_*`) in the "Incremental merkleization" section,
    monitoring knobs (`CST_METRICS_*`, `CST_SLO_*`,
    `CST_PROFILE_ON_BREACH`) in the "Monitoring" section,
    occupancy knobs (`CST_OCCUPANCY`) in the "Pipeline occupancy"
    section, flight-recorder knobs (`CST_FLIGHTREC*`) in the
    "Flight recorder" section,
    fault-plan knobs (`CST_FAULTS*`) in the "Resilience" section,
    checkpoint knobs (`CST_CHECKPOINT_*`) in the "Mesh resilience &
    checkpointing" section, mesh-sharding knobs (`CST_SHARD_*`) in
    the "Mesh sharding" section, DAS knobs (`CST_DAS_*`) in the
    "DAS / PeerDAS" section, and fork-choice knobs (`CST_FC_*`) in
    the "Fork choice" section — a subsystem's configuration surface
    must be documented where the subsystem is explained, not only in
    the flat table.  `repo` overrides the tree root (tests)."""
    repo = Path(repo) if repo is not None else PKG_ROOT.parent
    readme = repo / "README.md"
    readme_text = readme.read_text()
    documented = set(re.findall(r"\|\s*`(CST_[A-Z0-9_]+)`", readme_text))

    def section(title: str) -> str:
        m = re.search(rf"^## {title}$(.*?)(?=^## |\Z)", readme_text,
                      re.M | re.S)
        return m.group(1) if m else ""

    sectioned_prefixes = (("CST_SERVE_", "Serving", section("Serving")),
                          ("CST_MERKLE_", "Incremental merkleization",
                           section("Incremental merkleization")),
                          ("CST_METRICS_", "Monitoring",
                           section("Monitoring")),
                          ("CST_SLO_", "Monitoring",
                           section("Monitoring")),
                          ("CST_PROFILE_ON_BREACH", "Monitoring",
                           section("Monitoring")),
                          ("CST_OCCUPANCY", "Pipeline occupancy",
                           section("Pipeline occupancy")),
                          ("CST_FLIGHTREC", "Flight recorder",
                           section("Flight recorder")),
                          ("CST_FAULTS", "Resilience",
                           section("Resilience")),
                          ("CST_CHECKPOINT_",
                           "Mesh resilience & checkpointing",
                           section(re.escape(
                               "Mesh resilience & checkpointing"))),
                          ("CST_SHARD_", "Mesh sharding",
                           section("Mesh sharding")),
                          ("CST_DAS_", "DAS / PeerDAS",
                           section(re.escape("DAS / PeerDAS"))),
                          ("CST_FC_", "Fork choice",
                           section("Fork choice")))

    used: dict[str, str] = {}
    for path in sorted(repo.rglob("*.py")):
        if any(part in _SKIP_DIRS for part in path.parts):
            continue
        rel = str(path.relative_to(repo))
        try:
            text = path.read_text()
        except (OSError, UnicodeDecodeError):
            continue    # unreadable stray file — not ours to lint
        for m in _ENV_READ_RE.finditer(text):
            line = text.count("\n", 0, m.start()) + 1
            used.setdefault(m.group(1), f"{rel}:{line}")

    findings = []
    for name in sorted(set(used) - documented):
        findings.append(
            f"{used[name]}: env knob '{name}' read but not documented "
            f"in README.md's Environment knobs table")
    for name in sorted(documented - set(used)):
        findings.append(
            f"README.md: env knob '{name}' documented but never read "
            f"in the tree (stale table row?)")
    for name in sorted(set(used)):
        # a mention may carry an example value: `CST_SERVE_CHAOS=1`
        for prefix, title, text in sectioned_prefixes:
            if name.startswith(prefix) and not re.search(
                    rf"`{name}(?:=[^`]*)?`", text):
                findings.append(
                    f"{used[name]}: {title.lower()} knob '{name}' must "
                    f"also be documented in README.md's \"## {title}\" "
                    f"section")
    return findings


def main(argv=None) -> int:
    presets = ("minimal", "mainnet")
    total = 0
    # ONE dedup set for every finding source: overlapping fork chains
    # re-surface the same spec findings, and repeated runs of the env
    # pass must not double-print either (they used to bypass `seen`)
    seen: set[str] = set()

    def emit(finding: str) -> None:
        nonlocal total
        if finding not in seen:
            seen.add(finding)
            print(finding)
            total += 1

    for fork in BUILDABLE_FORKS:
        for preset in presets:
            for finding in lint_spec(fork, preset):
                emit(finding)
    for finding in lint_env_knobs():
        emit(finding)
    if total:
        print(f"spec lint: {total} finding(s)", file=sys.stderr)
        return 1
    print(f"spec lint: {len(BUILDABLE_FORKS) * len(presets)} "
          "spec builds clean (undefined-name + config-attribute + "
          "call-arity checks); env-knob table in sync")
    return 0


if __name__ == "__main__":
    sys.exit(main())
