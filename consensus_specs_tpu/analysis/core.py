"""Shared machinery for the device-path static analyzer.

Everything here is pure-AST and stdlib-only: no jax import, no spec
build, no numpy — the whole analysis pass must stay cheap enough to run
inside `make lint` and CI without moving the tier-1 wall-time budget
(ROADMAP).  The rule modules (`recompile`, `hostsync`, `dtype`,
`instrumentation`) consume the `ModuleModel` built here:

- jit surface discovery: `@jax.jit`-decorated functions (incl.
  `@partial(jax.jit, static_argnames=...)`), jit *factories*
  (functions returning `jax.jit(...)` or a jit-decorated local — the
  `_rlc_kernel(batch)` lru-cached pattern), and *traced bodies* (the
  function objects handed to `jax.jit`/`shard_map`, plus everything
  nested inside them);
- per-scope walks that do not leak into nested function scopes;
- two taint lattices: *raw-dim* (values derived from `len()`/`.shape`
  that have not been routed through the `_bucket` ladder — the
  recompile-hazard input) and *device* (values produced by a kernel
  dispatch — the host-sync input);
- inline suppressions: `# cst: allow(<rule-id>): <reason>` on the
  finding's line, or alone on the line above it.

Reporting contract: `file:line: rule-id: message`, exit 1 iff any
finding is unsuppressed.
"""

from __future__ import annotations

import ast
import io
import json
import re
import sys
import tokenize
from dataclasses import dataclass
from pathlib import Path

PKG_ROOT = Path(__file__).resolve().parent.parent

# rule-id -> what it catches (the README table mirrors this registry)
RULE_IDS = {
    "recompile-unbucketed-dim":
        "raw len()/shape value — or a mesh-shape device-count read "
        "(jax.device_count(), len(jax.devices())) — used as a jit "
        "compile key without the _bucket shape ladder / mesh_rung "
        "mesh-width ladder — every distinct value compiles a new "
        "XLA executable",
    "recompile-traced-branch":
        "Python if/while/assert on a traced value inside a jitted "
        "body — trace-time concretization error or silent retrace",
    "host-sync-item":
        ".item() on a device value — blocking device->host round-trip",
    "host-sync-coerce":
        "int()/float()/bool() on a device value — silently serializes "
        "the dispatch pipeline",
    "host-sync-np":
        "np.asarray()/np.array() on a device value — implicit device "
        "fetch",
    "host-sync-device-get":
        "jax.device_get() inside a device module",
    "host-sync-outside-settle":
        "blocking fetch outside the serve.futures settle seam — "
        "an `..._async(...).result()` chain beyond the synchronous "
        "facade, or block_until_ready in a device module",
    "device-const-at-import":
        "jnp array materialized at module import time — leaks tracers "
        "when the module is first imported inside a jit trace (keep "
        "module constants as numpy; jnp closes over them at trace "
        "time)",
    "dtype-int-literal":
        "untyped Python int literal >= 2**32 mixed into limb "
        "arithmetic — silent int32 overflow / weak-promotion hazard",
    "dtype-float":
        "float construction in integer limb-arithmetic modules",
    "dtype-implicit-cast":
        "jnp array construction without an explicit dtype — default "
        "dtype (float32 / platform int) corrupts limb lanes",
    "instr-uncovered-entry":
        "public kernel entry point without a telemetry span/counter — "
        "new kernels must not land unobservable",
    "instr-uncovered-cost":
        "public device-kernel entry point that never passes through "
        "the cost-capture seam (_dispatch or costmodel.capture) — the "
        "kernel stays invisible to the roofline/utilization layer",
    "exc-swallow-device":
        "bare/over-broad except in a device or serve module that "
        "neither re-raises nor poisons/records the exception — device "
        "failures must stay typed and visible, not read as success",
    "reqtrace-uncovered-submit":
        "ServeExecutor submit_* entry point that never mints a "
        "reqtrace.RequestContext — requests entering through it would "
        "be invisible to tail-latency attribution (see README Request "
        "tracing)",
    "instr-uncovered-dispatch-ledger":
        "dispatch/settle seam function (`_dispatch*` or "
        "`_settle_from_device` on the occupancy surface) that never "
        "reaches an occupancy-ledger call — device work flowing "
        "through it would be invisible to the busy/bubble attribution "
        "(see README Pipeline occupancy)",
    "metric-name-invalid":
        "telemetry.count/observe/gauge/span name outside the dotted-"
        "name convention, or two distinct names that collide into the "
        "same exposition family after Prometheus sanitization — the "
        "metrics endpoint would silently rewrite or merge their series "
        "(see README Monitoring)",
}

# --- file roles (which rule families run where) ------------------------------

ROLE_DEVICE = "device"   # host-sync + recompile (jit surface) rules
ROLE_KERNEL = "kernel"   # traced-branch applies to EVERY function
ROLE_LIMB = "limb"       # dtype discipline rules
ROLE_INSTR = "instr"     # instrumentation coverage rules
ROLE_EXC = "exc"         # exception-swallow discipline (serve +
                         # resilience modules; device files get it via
                         # ROLE_DEVICE)
ROLE_SERVE = "serve"     # request-tracing coverage of serve submit_*
                         # entry points (reqtrace-uncovered-submit)
ROLE_METRIC = "metric"   # metric-name discipline at every telemetry
                         # call site (metric-name-invalid) — runs over
                         # the whole package, since counters/spans are
                         # minted everywhere the device path runs
ROLE_LEDGER = "ledger"   # occupancy-ledger coverage of the dispatch /
                         # settle seams (instr-uncovered-dispatch-ledger)
ALL_ROLES = frozenset((ROLE_DEVICE, ROLE_KERNEL, ROLE_LIMB, ROLE_INSTR,
                       ROLE_EXC, ROLE_SERVE, ROLE_METRIC, ROLE_LEDGER))

# the device path named by the north star: every module that builds or
# dispatches XLA programs (oracle siblings under ops/bls are scanned too;
# they produce no findings because nothing in them touches jax)
DEVICE_GLOBS = ("ops/bls_batch/*.py", "ops/bls/*.py", "parallel/*.py")
DEVICE_FILES = ("ops/sha256_jax.py", "ops/fr_batch.py", "executor.py",
                "forkchoice/kernels.py", "forkchoice/store.py",
                "das/recover.py")
# exception-swallow discipline beyond the device files: the serving
# subsystem (where a swallowed error reads as a healthy request) and
# the resilience layer itself (which exists to keep failures typed).
# NOT merged into DEVICE_GLOBS — the host-sync/recompile families
# would misfire on serve/loadgen's sanctioned warmup settles.
EXC_GLOBS = ("serve/*.py", "resilience/*.py")
# limb-arithmetic modules under the dtype discipline
LIMB_FILES = (
    "ops/bls_batch/fq.py", "ops/bls_batch/tower.py",
    "ops/bls_batch/curve_jax.py", "ops/bls_batch/h2c_jax.py",
    "ops/bls_batch/pairing_jax.py",
)
# modules whose every function body is (or is traced into) device code:
# traced-branch checking extends beyond syntactic jit bodies here
KERNEL_FILES = LIMB_FILES + (
    "ops/sha256_jax.py", "ops/fr_batch.py", "parallel/epoch.py",
    "parallel/merkle.py",
)
# kernel entry-point surface: analyzed in chain order so the facade
# (ops/bls) can credit calls into the already-covered bls_batch
# entries; sha256_jax and fr_batch joined the surface with the
# cost-capture rule (instr-uncovered-cost) — their device entry points
# must stay visible to the roofline layer too; parallel/incremental.py
# joined with the incremental-merkleization kernels (merkle_incr@…);
# resilience/mesh.py + checkpoint.py joined with the recovery surfaces
# (their public entries must stay span-covered like every other path
# that can reach a device dispatch); parallel/partition.py joined with
# the partition-rule registry (the sharded epoch step's dispatch
# surface must stay observable like the kernels it wires up);
# das/verify.py joined with the DAS workload (its batched cell-proof
# entries chain fr_batch + bls_batch dispatches and must stay
# span/cost-covered like the kernels they compose);
# forkchoice/store.py + kernels.py joined with the fork-choice
# subsystem (the proto-array store's apply/head dispatches must stay
# span/cost-covered like every other device path);
# das/recover.py + ops/bls_batch/g1fft_jax.py joined with the FK20
# producer / erasure-recovery path (the G1-FFT and circulant-MSM
# entries plus the recover decode chain dispatch fr_batch + bls_batch
# kernels and must stay span/cost-covered);
# telemetry/occupancy.py + flightrec.py joined with the occupancy /
# flight-recorder subsystems (stdlib-only modules — they never dispatch,
# so the entry rules stay silent, but joining the surface keeps their
# sources under the same instrumentation sweep and the metric-name
# tree pass as every other observability layer)
INSTR_FILES = ("ops/bls_batch/__init__.py", "ops/bls/__init__.py",
               "ops/bls_batch/g1fft_jax.py",
               "ops/sha256_jax.py", "ops/fr_batch.py",
               "parallel/incremental.py", "parallel/partition.py",
               "resilience/mesh.py", "resilience/checkpoint.py",
               "das/verify.py", "das/recover.py",
               "forkchoice/store.py", "forkchoice/kernels.py",
               "telemetry/occupancy.py", "telemetry/flightrec.py")

# metric-name discipline runs over EVERY package module: instrument
# calls are minted from ops, serve, resilience, telemetry itself — a
# bad name or a sanitization collision can land anywhere
METRIC_GLOBS = ("*.py", "*/*.py", "*/*/*.py")

# request-tracing coverage surface: every `submit_*` entry point of a
# serve executor class must mint a reqtrace.RequestContext (directly or
# via a same-module helper it calls — the same call-graph propagation
# as instr-uncovered-entry), or requests entering through it would be
# invisible to tail-latency attribution
SERVE_FILES = ("serve/executor.py",)

# occupancy-ledger coverage surface: every dispatch/settle seam
# function (`_dispatch*`, `_settle_from_device`) in these modules must
# reach an occupancy-ledger call (begin_batch / note_kernel_* /
# note_settled) directly or via the local call graph — a future
# dispatch seam that skips the ledger would silently punch a hole in
# the busy/bubble attribution (instr-uncovered-dispatch-ledger)
OCCUPANCY_FILES = ("ops/bls_batch/__init__.py", "serve/executor.py",
                   "serve/futures.py")

# shape-laundering functions: a value that went through one of these is
# a bucketed compile key, not a raw dimension.  `mesh_rung` is the
# mesh-width form (parallel.partition): device-count reads are
# mesh-shape compile keys, quantized to the power-of-two ladder;
# `das_rung` is the DAS cell-batch form (ops.fr_batch); `fc_rung` is
# the fork-choice form (forkchoice.kernels: block-count,
# validator-count and attestation-batch ladders); `g1fft_rung` is the
# G1-FFT point-vector form (ops.bls_batch.g1fft_jax)
BUCKET_FUNCS = frozenset({"_bucket", "mesh_rung", "das_rung",
                          "fc_rung", "g1fft_rung"})

# device-pool probes whose results are mesh-shape compile keys: a jit
# factory keyed by a raw device count recompiles per topology without
# the mesh_rung ladder (len(jax.devices()) is caught by the generic
# len() taint)
DEVICE_COUNT_FUNCS = frozenset({"device_count", "local_device_count"})

# annotations that mark a parameter as a static (compile-time) value
_STATIC_TYPE_NAMES = frozenset({"int", "bool", "str", "bytes", "float"})
# attribute metadata reads that are static under trace
_SHAPE_ATTRS = frozenset({"shape", "dtype", "ndim", "size"})


@dataclass(frozen=True)
class Finding:
    file: str
    line: int
    rule: str
    message: str

    def render(self) -> str:
        return f"{self.file}:{self.line}: {self.rule}: {self.message}"


@dataclass
class Report:
    """Findings split by suppression state, plus the reasons given."""

    unsuppressed: list[Finding]
    suppressed: list[tuple[Finding, str | None]]
    files: int = 0

    def extend(self, other: "Report") -> None:
        self.unsuppressed.extend(other.unsuppressed)
        self.suppressed.extend(other.suppressed)
        self.files += other.files

    def to_json(self) -> dict:
        return {
            "schema": "cst-analysis-v1",
            "files": self.files,
            "finding_count": len(self.unsuppressed),
            "suppressed_count": len(self.suppressed),
            "suppressed_with_reason_count": sum(
                1 for _, reason in self.suppressed if reason),
            "findings": [vars(f) for f in self.unsuppressed],
            "suppressed": [dict(vars(f), reason=reason)
                           for f, reason in self.suppressed],
        }


# --- suppression comments ----------------------------------------------------

_ALLOW_RE = re.compile(
    r"cst:\s*allow\(\s*([a-z0-9-]+(?:\s*,\s*[a-z0-9-]+)*)\s*\)"
    r"(?:\s*:\s*(.*\S))?")


def parse_suppressions(src: str) -> dict[int, dict[str, str | None]]:
    """line -> {rule-id allowed on that line: reason}.

    A trailing comment covers its own line.  A comment alone on its
    line covers the next CODE line; its reason continues across the
    immediately following comment lines up to the next `cst: allow`
    comment, a blank line, or the code line — so stacked multi-line
    allow annotations each keep their full reason."""
    out: dict[int, dict[str, str | None]] = {}
    try:
        tokens = tokenize.generate_tokens(io.StringIO(src).readline)
        comments = [(t.start[0], t.string)
                    for t in tokens if t.type == tokenize.COMMENT]
    except (tokenize.TokenError, SyntaxError, IndentationError):
        comments = [(i + 1, line.strip())
                    for i, line in enumerate(src.splitlines())
                    if line.lstrip().startswith("#")]
    lines = src.splitlines()

    def add(line: int, rules: frozenset, reason: str | None):
        entry = out.setdefault(line, {})
        for rule in rules:
            entry[rule] = reason

    for row, text in comments:
        m = _ALLOW_RE.search(text)
        if not m:
            continue
        rules = frozenset(r.strip() for r in m.group(1).split(","))
        reason_parts = [m.group(2)] if m.group(2) else []
        add(row, rules, m.group(2))
        own_line = lines[row - 1] if row - 1 < len(lines) else ""
        if not own_line.strip().startswith("#"):
            continue                     # trailing comment: done
        # standalone: collect the reason's continuation lines, then
        # register on the next code line
        collecting = bool(reason_parts)
        nxt = row + 1
        while nxt <= len(lines):
            stripped = lines[nxt - 1].strip()
            if stripped.startswith("#"):
                if _ALLOW_RE.search(stripped):
                    collecting = False   # the next annotation starts
                elif collecting:
                    reason_parts.append(stripped.lstrip("#").strip())
                nxt += 1
            elif not stripped:
                collecting = False       # blank: unrelated code follows
                nxt += 1
            else:
                break
        reason = " ".join(reason_parts) if reason_parts else None
        add(nxt, rules, reason)
    return out


# --- AST helpers -------------------------------------------------------------


def _dotted(node) -> str | None:
    """'jax.jit'-style dotted name for Name/Attribute chains, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _is_jit_ref(node) -> bool:
    """Does this expression denote jax.jit (possibly partial-applied)?"""
    d = _dotted(node)
    if d in ("jit", "jax.jit"):
        return True
    if isinstance(node, ast.Call):
        fd = _dotted(node.func)
        if fd in ("partial", "functools.partial") and node.args:
            return _is_jit_ref(node.args[0])
        # jax.jit(static_argnums=...) decorator-factory form
        if fd in ("jit", "jax.jit"):
            return True
    return False


def _jit_static_names(dec, fn: ast.FunctionDef) -> set[str]:
    """static_argnames/static_argnums of a jit decorator -> param names."""
    if not isinstance(dec, ast.Call):
        return set()
    params = [a.arg for a in (list(fn.args.posonlyargs)
                              + list(fn.args.args))]
    static: set[str] = set()
    for kw in dec.keywords:
        v = kw.value
        if kw.arg == "static_argnames":
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                static.add(v.value)
            elif isinstance(v, (ast.Tuple, ast.List)):
                static |= {e.value for e in v.elts
                           if isinstance(e, ast.Constant)
                           and isinstance(e.value, str)}
        elif kw.arg == "static_argnums":
            nums = []
            if isinstance(v, ast.Constant) and isinstance(v.value, int):
                nums = [v.value]
            elif isinstance(v, (ast.Tuple, ast.List)):
                nums = [e.value for e in v.elts
                        if isinstance(e, ast.Constant)
                        and isinstance(e.value, int)]
            static |= {params[i] for i in nums if i < len(params)}
    return static


def _annotation_is_static(ann) -> bool:
    """int/bool/str-style annotations (incl. `str | None`, Optional[int])
    mark compile-time parameters."""
    if ann is None:
        return False
    if isinstance(ann, ast.Name):
        return ann.id in _STATIC_TYPE_NAMES
    if isinstance(ann, ast.Constant):
        if ann.value is None:
            return True
        return isinstance(ann.value, str) and ann.value in _STATIC_TYPE_NAMES
    if isinstance(ann, ast.BinOp) and isinstance(ann.op, ast.BitOr):
        return (_annotation_is_static(ann.left)
                and _annotation_is_static(ann.right))
    if isinstance(ann, ast.Subscript) and _dotted(ann.value) in (
            "Optional", "typing.Optional"):
        return _annotation_is_static(ann.slice)
    return False


def static_params(fn) -> set[str]:
    """Parameters that are static (compile-time) by annotation or by a
    literal int/bool/str default — `n: int`, `axis_name: str | None`,
    `unroll=False`."""
    args = (list(fn.args.posonlyargs) + list(fn.args.args)
            + list(fn.args.kwonlyargs))
    static = {a.arg for a in args if _annotation_is_static(a.annotation)}
    pos = list(fn.args.posonlyargs) + list(fn.args.args)
    defaults = list(fn.args.defaults)
    for a, d in zip(pos[len(pos) - len(defaults):], defaults):
        if isinstance(d, ast.Constant) and isinstance(
                d.value, (bool, int, str, bytes)):
            static.add(a.arg)
    for a, d in zip(fn.args.kwonlyargs, fn.args.kw_defaults):
        if isinstance(d, ast.Constant) and isinstance(
                d.value, (bool, int, str, bytes)):
            static.add(a.arg)
    return static


def param_names(fn) -> list[str]:
    out = [a.arg for a in (list(fn.args.posonlyargs) + list(fn.args.args)
                           + list(fn.args.kwonlyargs))]
    if fn.args.vararg:
        out.append(fn.args.vararg.arg)
    if fn.args.kwarg:
        out.append(fn.args.kwarg.arg)
    return out


def scope_nodes(fn):
    """Every node in `fn`'s own scope: yields nested function/class
    definition nodes themselves but does NOT descend into their bodies
    (they are separate scopes, analyzed on their own)."""
    stack = list(fn.body)
    while stack:
        node = stack.pop()
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            continue
        stack.extend(ast.iter_child_nodes(node))


def nonstatic_refs(expr, nonstatic: set[str]) -> list[ast.Name]:
    """Load-references to `nonstatic` names in `expr` that are NOT
    behind static metadata access (`x.shape`, `len(x)`, `isinstance`) —
    the references that would concretize a traced value."""
    out: list[ast.Name] = []

    def walk(node):
        if isinstance(node, ast.Attribute) and node.attr in _SHAPE_ATTRS:
            return
        if isinstance(node, ast.Call):
            fd = _dotted(node.func)
            if fd in ("len", "isinstance"):
                return
        if (isinstance(node, ast.Name)
                and isinstance(node.ctx, ast.Load)
                and node.id in nonstatic):
            out.append(node)
            return
        for child in ast.iter_child_nodes(node):
            walk(child)

    walk(expr)
    return out


# --- the module model --------------------------------------------------------


class ModuleModel:
    """One parsed device-path module with its jit surface resolved."""

    def __init__(self, src: str, path: str, roles: frozenset):
        self.src = src
        self.path = path
        self.roles = roles
        self.tree = ast.parse(src)
        self.suppressions = parse_suppressions(src)

        # every function definition anywhere in the module, by name
        self.func_index: dict[str, list[ast.FunctionDef]] = {}
        self.all_funcs: list[ast.FunctionDef] = []
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self.all_funcs.append(node)
                self.func_index.setdefault(node.name, []).append(node)

        # jit-decorated functions -> their static param names
        self.jit_decorated: dict[ast.FunctionDef, set[str]] = {}
        for fn in self.all_funcs:
            for dec in fn.decorator_list:
                if _is_jit_ref(dec):
                    self.jit_decorated[fn] = _jit_static_names(dec, fn)
                    break

        # functions handed to jit/shard_map by reference: jax.jit(run),
        # jax.shard_map(local, ...)
        referenced: set[str] = set()
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            fd = _dotted(node.func)
            is_wrap = (fd in ("jit", "jax.jit")
                       or (fd or "").split(".")[-1] == "shard_map")
            if is_wrap and isinstance(node.args[0], ast.Name):
                referenced.add(node.args[0].id)

        # traced bodies: decorated + referenced, plus everything nested
        # inside them; traced_params maps each body to the union of its
        # own and its enclosing traced bodies' non-static params
        self.traced_params: dict[ast.FunctionDef, set[str]] = {}
        roots = list(self.jit_decorated) + [
            fn for name in referenced for fn in self.func_index.get(name, [])]
        for root in roots:
            inherited: set[str] = set()
            self._mark_traced(root, inherited)
        self.jit_bodies = set(self.traced_params)

        # jit factories: module-level functions returning jax.jit(...)
        # or a jit-decorated local function
        self.jit_factories: set[str] = set()
        for node in self.tree.body:
            if not isinstance(node, ast.FunctionDef):
                continue
            for sub in scope_nodes(node):
                if not isinstance(sub, ast.Return) or sub.value is None:
                    continue
                v = sub.value
                if isinstance(v, ast.Call) and _is_jit_ref(v.func):
                    self.jit_factories.add(node.name)
                elif isinstance(v, ast.Name) and any(
                        f in self.jit_decorated
                        for f in self.func_index.get(v.id, [])):
                    self.jit_factories.add(node.name)

    def _mark_traced(self, fn, inherited: set[str]) -> None:
        own = (inherited
               | (set(param_names(fn)) - static_params(fn)
                  - self.jit_decorated.get(fn, set())))
        prev = self.traced_params.get(fn)
        if prev is not None and own <= prev:
            return
        self.traced_params[fn] = own | (prev or set())
        for node in scope_nodes(fn):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._mark_traced(node, self.traced_params[fn])

    def nested_funcs(self, fn):
        return [n for n in scope_nodes(fn)
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]

    def is_device_function(self, fn) -> bool:
        """Does this function build jax computations (jnp/lax use, the
        `jnp = _jnp()` idiom, or membership in a traced body)?"""
        if fn in self.jit_bodies:
            return True
        for node in scope_nodes(fn):
            if isinstance(node, ast.Name) and node.id in ("jnp", "lax"):
                return True
            if isinstance(node, ast.Attribute) and (
                    _dotted(node) or "").startswith("jax."):
                return True
            if (isinstance(node, ast.Call)
                    and _dotted(node.func) == "_jnp"):
                return True
        return False

    # --- per-scope dataflow ------------------------------------------------

    def factory_aliases(self, fn) -> set[str]:
        """Local names that (conditionally) hold a jit factory:
        `kernel = _rlc_kernel_h2c if device_h2c else _rlc_kernel`."""
        aliases = set(self.jit_factories)

        def is_factory_expr(e) -> bool:
            if isinstance(e, ast.Name):
                return e.id in aliases
            if isinstance(e, ast.IfExp):
                return is_factory_expr(e.body) and is_factory_expr(e.orelse)
            return False

        for _ in range(2):
            for node in scope_nodes(fn):
                if (isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)
                        and is_factory_expr(node.value)):
                    aliases.add(node.targets[0].id)
        return aliases

    def _scope_assignments(self, fn):
        """Assignment statements of `fn`'s scope in SOURCE order —
        `scope_nodes` is a LIFO walk, and taint gen/kill is
        order-sensitive (`n = xs.shape[0]; n = _bucket(n)` must end
        clean, not tainted)."""
        assigns = [n for n in scope_nodes(fn)
                   if isinstance(n, (ast.Assign, ast.AugAssign,
                                     ast.AnnAssign))]
        return sorted(assigns, key=lambda n: (n.lineno, n.col_offset))

    def raw_dim_tainted(self, fn) -> set[str]:
        """Names carrying a raw dimension: derived from len()/`.shape`
        without passing through a BUCKET_FUNCS call."""
        tainted: set[str] = set()

        def expr_tainted(e) -> bool:
            if (isinstance(e, ast.Call)
                    and _dotted(e.func) in BUCKET_FUNCS):
                return False            # the ladder launders the value
            for node in ast.walk(e):
                if (isinstance(node, ast.Call)
                        and _dotted(node.func) == "len"):
                    return True
                if (isinstance(node, ast.Call)
                        and (_dotted(node.func) or "").split(".")[-1]
                        in DEVICE_COUNT_FUNCS):
                    return True         # mesh-shape compile key
                if (isinstance(node, ast.Attribute)
                        and node.attr == "shape"):
                    return True
                if (isinstance(node, ast.Name)
                        and isinstance(node.ctx, ast.Load)
                        and node.id in tainted):
                    return True
            return False

        def bind(target, hot: bool):
            for n in ast.walk(target):
                if isinstance(n, ast.Name) and isinstance(
                        n.ctx, (ast.Store,)):
                    if hot:
                        tainted.add(n.id)
                    else:
                        tainted.discard(n.id)

        # two source-ordered passes: the second propagates through
        # loop-carried bindings while rebinding-through-_bucket kills
        for _ in range(2):
            for node in self._scope_assignments(fn):
                if isinstance(node, ast.Assign):
                    hot = expr_tainted(node.value)
                    for t in node.targets:
                        bind(t, hot)
                elif isinstance(node, ast.AugAssign):
                    if expr_tainted(node.value):
                        bind(node.target, True)
                elif node.value:        # AnnAssign
                    bind(node.target, expr_tainted(node.value))
        return tainted

    def device_producing(self, call, aliases: set[str]) -> bool:
        """Calls whose result lives on device: `_dispatch(...)`, a
        jitted local, `factory(B)(args)`, jax.block_until_ready."""
        if not isinstance(call, ast.Call):
            return False
        f = call.func
        fd = _dotted(f)
        if fd == "_dispatch" or (fd or "").endswith("block_until_ready"):
            return True
        if isinstance(f, ast.Name):
            if any(d in self.jit_decorated
                   for d in self.func_index.get(f.id, [])):
                return True
        if isinstance(f, ast.Call):        # factory(B)(args)
            inner = f.func
            if isinstance(inner, ast.Name) and inner.id in aliases:
                return True
        return False

    def device_tainted(self, fn, aliases: set[str]) -> set[str]:
        """Names bound (directly, by unpack, or as a comprehension
        target over a tainted iterable) to device values."""
        tainted: set[str] = set()

        def bind_names(target):
            for n in ast.walk(target):
                if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store):
                    tainted.add(n.id)

        for _ in range(2):
            for node in scope_nodes(fn):
                if isinstance(node, ast.Assign) and self.device_producing(
                        node.value, aliases):
                    for t in node.targets:
                        bind_names(t)
                elif isinstance(node, ast.comprehension):
                    it = node.iter
                    if (isinstance(it, ast.Name) and it.id in tainted) \
                            or self.device_producing(it, aliases):
                        bind_names(node.target)
                elif isinstance(node, ast.For):
                    it = node.iter
                    if (isinstance(it, ast.Name) and it.id in tainted) \
                            or self.device_producing(it, aliases):
                        bind_names(node.target)
        return tainted


# --- runner ------------------------------------------------------------------


def _apply_suppressions(model: ModuleModel,
                        findings: list[Finding]) -> Report:
    unsup: list[Finding] = []
    sup: list[tuple[Finding, str | None]] = []
    for f in sorted(findings, key=lambda f: (f.file, f.line, f.rule)):
        entry = model.suppressions.get(f.line)
        if entry and f.rule in entry:
            sup.append((f, entry[f.rule]))
        else:
            unsup.append(f)
    return Report(unsup, sup, files=1)


def analyze_source(src: str, path: str = "<snippet>",
                   roles: frozenset = ALL_ROLES,
                   external_covered: frozenset = frozenset(),
                   external_device: frozenset = frozenset(),
                   external_cost: frozenset = frozenset()) -> Report:
    """Analyze one module's source under the given roles.  Returns the
    suppression-resolved report; `external_covered`/`external_device`/
    `external_cost` feed the instrumentation rules' cross-module
    resolution."""
    from . import (dtype, excswallow, hostsync, instrumentation,
                   metricnames, recompile)

    model = ModuleModel(src, path, roles)
    findings: list[Finding] = []
    if ROLE_DEVICE in roles:
        findings += recompile.check(model)
        findings += hostsync.check(model)
    if ROLE_DEVICE in roles or ROLE_EXC in roles:
        findings += excswallow.check(model)
    if ROLE_LIMB in roles:
        findings += dtype.check(model)
    if ROLE_INSTR in roles:
        findings += instrumentation.check(
            model, external_covered, external_device, external_cost)[0]
    if ROLE_SERVE in roles:
        findings += instrumentation.check_reqtrace(model)
    if ROLE_LEDGER in roles:
        findings += instrumentation.check_occupancy(model)
    if ROLE_METRIC in roles:
        findings += metricnames.check(model)
    return _apply_suppressions(model, findings)


def _tree_files(root: Path) -> list[tuple[Path, frozenset]]:
    files: dict[Path, set] = {}
    for pattern in DEVICE_GLOBS:
        for p in sorted(root.glob(pattern)):
            files.setdefault(p, set()).add(ROLE_DEVICE)
    for rel in DEVICE_FILES:
        p = root / rel
        if p.exists():
            files.setdefault(p, set()).add(ROLE_DEVICE)
    for rel in LIMB_FILES:
        p = root / rel
        if p.exists():
            files.setdefault(p, set()).add(ROLE_LIMB)
    for rel in KERNEL_FILES:
        p = root / rel
        if p.exists():
            files.setdefault(p, set()).add(ROLE_KERNEL)
    for pattern in EXC_GLOBS:
        for p in sorted(root.glob(pattern)):
            files.setdefault(p, set()).add(ROLE_EXC)
    for rel in SERVE_FILES:
        p = root / rel
        if p.exists():
            files.setdefault(p, set()).add(ROLE_SERVE)
    for rel in OCCUPANCY_FILES:
        p = root / rel
        if p.exists():
            files.setdefault(p, set()).add(ROLE_LEDGER)
    for pattern in METRIC_GLOBS:
        for p in sorted(root.glob(pattern)):
            files.setdefault(p, set()).add(ROLE_METRIC)
    return [(p, frozenset(r)) for p, r in sorted(files.items())]


def _instr_chain(root: Path | None = None):
    """The ONE implementation of the ordered instrumentation pass over
    INSTR_FILES (ops/bls_batch first, so the facade's calls into its
    covered entry points count as coverage).  Returns, per file:
    (resolved_path, model, findings, entry_covered, entry_device,
    entry_cost) where the entry sets are the chained inputs that file's
    pass started from — both the tree run and spot runs consume this."""
    from . import instrumentation

    root = Path(root) if root is not None else PKG_ROOT
    covered: frozenset = frozenset()
    device: frozenset = frozenset()
    cost: frozenset = frozenset()
    out = []
    for rel in INSTR_FILES:
        path = root / rel
        if not path.exists():
            continue
        model = ModuleModel(path.read_text(),
                            str(path.relative_to(root.parent)),
                            frozenset({ROLE_INSTR}))
        findings, cov, dev, cst = instrumentation.check(
            model, covered, device, cost)
        out.append((path.resolve(), model, findings, covered, device,
                    cost))
        covered, device, cost = (frozenset(cov), frozenset(dev),
                                 frozenset(cst))
    return out


def analyze_tree(root: Path | None = None) -> Report:
    """Run every applicable rule family over the device path."""
    root = Path(root) if root is not None else PKG_ROOT
    repo = root.parent
    report = Report([], [])
    for path, roles in _tree_files(root):
        rel = str(path.relative_to(repo))
        report.extend(analyze_source(path.read_text(), rel, roles))

    for _, model, findings, _, _, _ in _instr_chain(root):
        sub = _apply_suppressions(model, findings)
        sub.files = 0           # already counted in the device pass
        report.extend(sub)
    return report


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    json_out = None
    if "--json" in argv:
        i = argv.index("--json")
        try:
            json_out = argv[i + 1]
        except IndexError:
            print("--json needs a path", file=sys.stderr)
            return 2
        del argv[i:i + 2]

    if argv:
        # package files keep their tree-mode roles (so a spot run of a
        # real module agrees with the tree run); anything else — e.g. a
        # test fixture — gets every rule family
        tree_roles = {p.resolve(): roles
                      for p, roles in _tree_files(PKG_ROOT)}
        instr_inputs = {path: (cov, dev, cst)
                        for path, _, _, cov, dev, cst in _instr_chain()}
        report = Report([], [])
        for arg in argv:
            p = Path(arg)
            try:
                src = p.read_text()
            except OSError as exc:
                print(f"{p}: cannot read ({exc})", file=sys.stderr)
                return 2
            try:
                resolved = p.resolve()
                roles = tree_roles.get(resolved, ALL_ROLES)
                ext_cov, ext_dev, ext_cost = instr_inputs.get(
                    resolved, (frozenset(), frozenset(), frozenset()))
                if resolved in instr_inputs:
                    roles = roles | {ROLE_INSTR}
                report.extend(analyze_source(src, str(p), roles,
                                             ext_cov, ext_dev, ext_cost))
            except SyntaxError as exc:
                print(f"{p}: not parseable python ({exc})",
                      file=sys.stderr)
                return 2
    else:
        report = analyze_tree()

    if json_out:
        out = Path(json_out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text(json.dumps(report.to_json(), indent=2) + "\n")

    for f in report.unsuppressed:
        print(f.render())
    n_sup = len(report.suppressed)
    n_reason = sum(1 for _, r in report.suppressed if r)
    if report.unsuppressed:
        print(f"device-path analysis: {len(report.unsuppressed)} "
              f"finding(s), {n_sup} suppressed", file=sys.stderr)
        return 1
    print(f"device-path analysis: clean — {report.files} file(s), "
          f"{n_sup} finding(s) suppressed ({n_reason} with a reason)")
    return 0
