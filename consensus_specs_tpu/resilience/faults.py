"""Deterministic fault injection at the sanctioned device-path seams.

A fault plan is a seeded, schema-validated description of WHICH faults
fire WHERE: every injection is driven by one `random.Random(seed)` and
per-rule counters, so a chaos round replays bit-for-bit and a test can
assert the exact blast radius (every fired fault is logged with its
injection site and key).

Sites (`SITES`) — the four seams the hooks live at:

    dispatch        `ops.bls_batch._dispatch` (key = kernel name, e.g.
                    `rlc_h2c@8`), the mesh-sharded entry point
                    `batch_verify_sharded_async` (key =
                    `rlc_sharded@<devices>x<per_shard>` — the
                    `device_loss` chaos target `resilience.mesh`
                    recovers from), `ops.sha256_jax` (key =
                    `sha256_merkle@d<depth>`), and the fork-choice
                    store's kernels (keys `fc_weights@b<B>v<V>` /
                    `fc_head@<NB>` — the serve `head` lane's
                    breaker→spec-oracle chaos target) — the
                    jitted-kernel dispatch boundary
    future_settle   `serve.futures.DeviceFuture` device-backed settle
                    (key = "device") — the device→host transfer
    serve_pump      `ServeExecutor._dispatch_one` (key = request kind:
                    verify/pairing/msm/sha256/fr/proof) — the serving
                    batch boundary
    merkle_update   `parallel.incremental.update_dirty` (key =
                    `u<rung>d<depth>`) — the persistent-layer re-hash

Kinds (`KINDS`):

    raise           raise `FaultInjected` at the seam (a dispatch/prep
                    exception)
    latency         sleep `latency_ms` at the seam (slow device /
                    saturated interconnect)
    compile_fail    raise on the FIRST sighting of each matching key
                    (a kernel whose XLA compile dies); later calls of
                    the same key pass — the "first call per shape"
                    failure mode
    corrupt         corrupt the seam's output value (bit-flip the low
                    bit of integer/bool lanes, NaN float lanes; tuples
                    corrupt their LAST element — the root layer of a
                    Merkle update, the Z limb of a point)
    device_loss     raise `MeshDeviceLost` (a mesh device dropping out
                    mid-round)

Plan forms accepted by `load_plan` / the `CST_FAULTS` env knob:

    a JSON object   {"seed": 7, "faults": [{"site": "dispatch",
                     "kind": "raise", "key": "rlc_*", "count": 3}]}
    a file path     containing that JSON
    a spec string   "seed=7;dispatch:raise:key=rlc_*:count=3;
                     serve_pump:latency:latency_ms=20:p=0.5"

Rule fields: `key` (fnmatch glob over the seam key, default "*"), `p`
(fire probability, seeded — default 1.0), `count` (max fires, default
unlimited), `after` (skip the first N matching events), `latency_ms`,
`mode` ("bitflip" | "nan" for corrupt).

Gating contract (the telemetry pattern): everything is OFF until a plan
is `install()`ed, `active()` is one module-global read, and every hook
guards with it — the disabled hot path stays provably free of fault
machinery (no-op bound test in tests/test_resilience.py).  Stdlib-only
at import; numpy is imported lazily inside `corrupt` (which only runs
with a plan installed and jax already live).
"""

from __future__ import annotations

import fnmatch
import json
import os
import random
import threading
import time

from .. import telemetry
from ..telemetry import flightrec

SITES = ("dispatch", "future_settle", "serve_pump", "merkle_update")
KINDS = ("raise", "latency", "compile_fail", "corrupt", "device_loss")
MODES = ("bitflip", "nan")

_lock = threading.Lock()


class FaultInjected(RuntimeError):
    """A fault fired at a sanctioned seam.  Carries the injection site,
    the seam key, and the fault kind so tests (and the serve executor's
    failure accounting) can assert exact blast radius."""

    def __init__(self, site: str, key: str, kind: str):
        super().__init__(f"injected {kind} fault at {site}:{key}")
        self.site = site
        self.key = key
        self.kind = kind


class MeshDeviceLost(FaultInjected):
    """A `device_loss` fault — models a mesh device dropping out (the
    failure XLA surfaces as a dead-executable error mid-round)."""


class _Rule:
    __slots__ = ("site", "kind", "key", "p", "count", "after",
                 "latency_ms", "mode", "fired", "seen", "hit_keys")

    def __init__(self, site, kind, key="*", p=1.0, count=None, after=0,
                 latency_ms=0.0, mode=None):
        self.site = site
        self.kind = kind
        self.key = key
        self.p = float(p)
        self.count = count
        self.after = int(after)
        self.latency_ms = float(latency_ms)
        self.mode = mode
        self.fired = 0
        self.seen = 0
        self.hit_keys: set[str] = set()   # compile_fail: first-per-key

    def describe(self) -> dict:
        out = {"site": self.site, "kind": self.kind, "key": self.key}
        if self.p < 1.0:
            out["p"] = self.p
        if self.count is not None:
            out["count"] = self.count
        if self.after:
            out["after"] = self.after
        if self.latency_ms:
            out["latency_ms"] = self.latency_ms
        if self.mode:
            out["mode"] = self.mode
        return out


class FaultPlan:
    """A validated set of fault rules plus the seeded RNG and the
    injection log.  Build via `load_plan`; activate via `install`."""

    def __init__(self, rules: list[_Rule], seed: int = 0):
        self.rules = list(rules)
        self.seed = int(seed)
        self.rng = random.Random(self.seed)
        self.log: list[dict] = []

    def describe(self) -> dict:
        """Compact JSON-able summary (rides the resilience bench block)."""
        return {"seed": self.seed,
                "faults": [r.describe() for r in self.rules]}

    def _take(self, site: str, key: str, kinds: tuple) -> list[_Rule]:
        """Consume one seam event: advance matching rules' counters and
        return the ones that fire (deterministic given the seed and the
        event order)."""
        fired = []
        with _lock:
            for rule in self.rules:
                if rule.kind not in kinds or rule.site != site:
                    continue
                if not fnmatch.fnmatchcase(key, rule.key):
                    continue
                rule.seen += 1
                if rule.seen <= rule.after:
                    continue
                if rule.count is not None and rule.fired >= rule.count:
                    continue
                if rule.kind == "compile_fail":
                    if key in rule.hit_keys:
                        continue
                    rule.hit_keys.add(key)
                if rule.p < 1.0 and self.rng.random() >= rule.p:
                    continue
                rule.fired += 1
                self.log.append({"site": site, "key": key,
                                 "kind": rule.kind})
                fired.append(rule)
        return fired


def validate_plan(obj) -> list[str]:
    """Schema check for a fault-plan JSON object; returns a list of
    problems (empty == valid) — the contract `load_plan` enforces and
    tests/test_resilience.py pins."""
    problems: list[str] = []
    if not isinstance(obj, dict):
        return [f"fault plan is {type(obj).__name__}, not dict"]
    seed = obj.get("seed", 0)
    if not isinstance(seed, int) or isinstance(seed, bool):
        problems.append(f"'seed' must be an int, got {seed!r}")
    faults = obj.get("faults")
    if not isinstance(faults, list) or not faults:
        return problems + ["'faults' must be a non-empty list"]
    for i, f in enumerate(faults):
        where = f"faults[{i}]"
        if not isinstance(f, dict):
            problems.append(f"{where}: not a dict")
            continue
        if f.get("site") not in SITES:
            problems.append(f"{where}: 'site' must be one of {SITES}, "
                            f"got {f.get('site')!r}")
        if f.get("kind") not in KINDS:
            problems.append(f"{where}: 'kind' must be one of {KINDS}, "
                            f"got {f.get('kind')!r}")
        key = f.get("key", "*")
        if not isinstance(key, str) or not key:
            problems.append(f"{where}: 'key' must be a non-empty glob "
                            f"string, got {key!r}")
        p = f.get("p", 1.0)
        if not isinstance(p, (int, float)) or isinstance(p, bool) \
                or not (0.0 < p <= 1.0):
            problems.append(f"{where}: 'p' must be in (0, 1], got {p!r}")
        count = f.get("count")
        if count is not None and (not isinstance(count, int)
                                  or isinstance(count, bool) or count < 1):
            problems.append(f"{where}: 'count' must be a positive int "
                            f"or absent, got {count!r}")
        after = f.get("after", 0)
        if not isinstance(after, int) or isinstance(after, bool) \
                or after < 0:
            problems.append(f"{where}: 'after' must be a non-negative "
                            f"int, got {after!r}")
        lat = f.get("latency_ms", 0.0)
        if not isinstance(lat, (int, float)) or isinstance(lat, bool) \
                or lat < 0:
            problems.append(f"{where}: 'latency_ms' must be a "
                            f"non-negative number, got {lat!r}")
        if f.get("kind") == "latency" and not lat:
            problems.append(f"{where}: a 'latency' fault needs a "
                            f"positive 'latency_ms'")
        mode = f.get("mode")
        if mode is not None and mode not in MODES:
            problems.append(f"{where}: 'mode' must be one of {MODES} "
                            f"or absent, got {mode!r}")
        unknown = set(f) - {"site", "kind", "key", "p", "count", "after",
                            "latency_ms", "mode"}
        if unknown:
            problems.append(f"{where}: unknown field(s) "
                            f"{sorted(unknown)}")
    return problems


def _parse_spec(text: str) -> dict:
    """Compact spec string -> plan dict (see module docstring)."""
    plan: dict = {"faults": []}
    for seg in text.split(";"):
        seg = seg.strip()
        if not seg:
            continue
        if seg.startswith("seed="):
            try:
                plan["seed"] = int(seg[len("seed="):])
            except ValueError:
                raise ValueError(f"fault spec: bad seed segment {seg!r}")
            continue
        parts = seg.split(":")
        if len(parts) < 2:
            raise ValueError(
                f"fault spec segment {seg!r} needs at least site:kind")
        fault: dict = {"site": parts[0], "kind": parts[1]}
        for opt in parts[2:]:
            k, eq, v = opt.partition("=")
            if not eq:
                raise ValueError(f"fault spec option {opt!r} is not k=v")
            if k in ("key", "mode"):
                fault[k] = v
            elif k in ("count", "after"):
                try:
                    fault[k] = int(v)
                except ValueError:
                    raise ValueError(f"fault spec: {k}={v!r} not an int")
            elif k in ("p", "latency_ms"):
                try:
                    fault[k] = float(v)
                except ValueError:
                    raise ValueError(f"fault spec: {k}={v!r} not a number")
            else:
                raise ValueError(f"fault spec: unknown option {k!r}")
        plan["faults"].append(fault)
    return plan


def load_plan(source) -> FaultPlan:
    """Build a validated `FaultPlan` from a dict, a JSON string, a JSON
    file path, or a compact spec string.  Raises ValueError (with every
    schema problem listed) on an invalid plan — a chaos round must not
    half-run a typo'd plan."""
    if isinstance(source, FaultPlan):
        return source
    if isinstance(source, dict):
        obj = source
    elif isinstance(source, str):
        text = source.strip()
        if text.startswith("{"):
            obj = json.loads(text)
        elif os.path.exists(text):
            with open(text) as f:
                obj = json.load(f)
        else:
            obj = _parse_spec(text)
    else:
        raise ValueError(f"cannot load a fault plan from "
                         f"{type(source).__name__}")
    problems = validate_plan(obj)
    if problems:
        raise ValueError("invalid fault plan: " + "; ".join(problems))
    rules = [_Rule(**f) for f in obj["faults"]]
    return FaultPlan(rules, seed=obj.get("seed", 0))


# --- the gate (the telemetry `enabled()` pattern) ----------------------------

_plan: FaultPlan | None = None


def active() -> bool:
    """True while a fault plan is installed.  The ONE check every seam
    hook guards with — disabled mode is this module-global read."""
    return _plan is not None


def current() -> FaultPlan | None:
    return _plan


def install(plan) -> FaultPlan:
    """Activate a fault plan (any `load_plan` source form)."""
    global _plan
    plan = load_plan(plan)
    _plan = plan
    return plan


def clear() -> None:
    """Deactivate fault injection (the recovery phase of a chaos round)."""
    global _plan
    _plan = None


def plan_from_env_source() -> str | None:
    """The raw CST_FAULTS plan source (not yet loaded), or None when
    the knob is unset — the chaos harness's plan-precedence read."""
    return os.environ.get("CST_FAULTS") or None


def install_from_env() -> bool:
    """Install the `CST_FAULTS` plan when the knob is set; returns
    whether injection is now active.  Call sites: the chaos harness —
    never at import."""
    source = os.environ.get("CST_FAULTS")
    if not source:
        return active()
    install(source)
    return True


def injections() -> list[dict]:
    """The fired-fault log (site/key/kind per injection) — the blast-
    radius assertion surface."""
    return list(_plan.log) if _plan is not None else []


# --- the seam hooks ----------------------------------------------------------


def maybe_inject(site: str, key: str = "") -> None:
    """The raise/latency/compile_fail/device_loss seam hook.  No-op
    without a plan; with one, consumes a (site, key) event and applies
    every firing rule — latency sleeps, the raising kinds raise (tagged
    with site/key/kind)."""
    plan = _plan
    if plan is None:
        return
    for rule in plan._take(site, key, ("raise", "latency",
                                       "compile_fail", "device_loss")):
        telemetry.count(f"faults.injected.{site}")
        flightrec.record("fault_injected", site=site, key=key,
                         fault=rule.kind)
        if rule.kind == "latency":
            time.sleep(rule.latency_ms / 1e3)
        elif rule.kind == "device_loss":
            raise MeshDeviceLost(site, key, rule.kind)
        else:
            raise FaultInjected(site, key, rule.kind)


def corrupt(site: str, key: str, value):
    """The corrupted-output seam hook: returns `value`, possibly with a
    firing corrupt rule applied (bit-flip integer/bool lanes, NaN float
    lanes; tuples/lists corrupt their last element).  Device arrays stay
    on device — the corruption is expressed through the array's own
    operators, so a jnp value corrupts via one extra fused op."""
    plan = _plan
    if plan is None:
        return value
    for rule in plan._take(site, key, ("corrupt",)):
        telemetry.count(f"faults.injected.{site}")
        flightrec.record("fault_injected", site=site, key=key,
                         fault="corrupt", mode=rule.mode)
        value = _corrupt_value(value, rule.mode)
    return value


def _corrupt_value(value, mode):
    if isinstance(value, (tuple, list)):
        if not value:
            return value
        head, last = list(value[:-1]), _corrupt_value(value[-1], mode)
        return type(value)(head + [last]) if isinstance(value, list) \
            else tuple(head) + (last,)
    import numpy as np

    dt = getattr(value, "dtype", None)
    if dt is None:
        if isinstance(value, bool):
            return not value
        if isinstance(value, int):
            return value ^ 1
        if isinstance(value, float):
            return float("nan")
        return value
    if np.issubdtype(dt, np.bool_):
        return ~value
    if np.issubdtype(dt, np.floating):
        return value * float("nan")
    if np.issubdtype(dt, np.integer):
        if mode == "nan":
            # integer lanes have no NaN — bit-flip is the only honest
            # corruption there
            pass
        return value ^ np.asarray(1, dtype=dt)
    return value
