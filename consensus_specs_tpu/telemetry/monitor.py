"""Live SLO watchdog — continuous rule evaluation over the serve fleet.

`metrics_export.py` makes the registry scrapeable; this module makes it
WATCHED.  A declarative rule set (the `CST_SLO_RULES` knob — JSON / file
path / compact spec string, the same source forms as `CST_FAULTS`) is
evaluated on a daemon tick against rolling windows of the live signals,
with breach→clear hysteresis, and every transition is a typed, counted
`SloBreach` event carrying the evidence the pod round needs: the
offending value, the margin past the threshold, the worst-N reqtrace
exemplars at the moment of breach, and (opt-in, `CST_PROFILE_ON_BREACH`,
at most once per rule per round) a bounded `jax.profiler` trace grab.

Signals (`SIGNALS`) the evaluator resolves per tick:

    serve.p50_ms / serve.p99_ms   rolling-window request latency, per
                                  kind (`{kind=...}`) or worst-kind
    serve.throughput_rps          completed requests/s over the rule's
                                  window (per kind or overall)
    serve.queue_depth             live executor queue depth
    serve.queue_age_s             age of the oldest queued request
    serve.inflight_batches        batches in flight
    breaker.flaps                 breaker state transitions inside the
                                  rule's window (flap-rate alarm)
    mem.slope_mb_s                per-device memory-watermark slope
                                  over the window, worst device (leak
                                  detection)
    counter.<name>                rate/s of any telemetry counter

Rule grammar (compact spec form; segments joined by `;`):

    serve.p99_ms{kind=verify}<500:for=2:clear=3
    serve.throughput_rps>=100:window_s=10
    mem.slope_mb_s<8:name=leak-watch

`op` ∈ {<, <=, >, >=} states the HEALTHY condition — a rule breaches
when the comparison FAILS for `for` consecutive ticks and clears after
`clear` consecutive healthy ticks (hysteresis is what keeps a noisy
signal from flapping the alarm).  JSON form:

    {"tick_s": 1.0, "rules": [{"metric": "serve.p99_ms",
      "kind": "verify", "op": "<", "threshold": 500,
      "for": 2, "clear": 3, "window_s": 10.0, "name": "p99-verify"}]}

Gating contract (the faults pattern): OFF until `install()`, `active()`
is one module-global read, `install_from_env()` rejects a malformed
`CST_SLO_RULES` with a counted warning instead of killing the round
(`load_rules()` raises, listing every problem, for programmatic use).
Stdlib-only; jax is only read out of `sys.modules` for the breach
profiler grab (a telemetry layer must not initialize a backend).
"""

from __future__ import annotations

import collections
import json
import os
import re
import sys
import threading
import time

from . import core, costmodel, flightrec, metrics_export, occupancy, \
    reqtrace

OPS = ("<", "<=", ">", ">=")
SIGNALS = ("serve.p50_ms", "serve.p99_ms", "serve.throughput_rps",
           "serve.queue_depth", "serve.queue_age_s",
           "serve.inflight_batches", "breaker.flaps", "mem.slope_mb_s",
           "serve.busy_frac")
# signals that accept a {kind=...} label
_KIND_SIGNALS = ("serve.p50_ms", "serve.p99_ms", "serve.throughput_rps")

_MAX_EVENTS = 2_000          # breach/clear event log cap; drops counted
_HIST_LEN = 512              # per-signal rolling-history samples
_PROFILE_GRAB_S = 2.0        # bounded breach profiler capture

_OP_FNS = {
    "<": lambda v, t: v < t,
    "<=": lambda v, t: v <= t,
    ">": lambda v, t: v > t,
    ">=": lambda v, t: v >= t,
}
# margin past the threshold, positive while breaching
_MARGINS = {
    "<": lambda v, t: v - t,
    "<=": lambda v, t: v - t,
    ">": lambda v, t: t - v,
    ">=": lambda v, t: t - v,
}


class SloBreach:
    """One SLO transition: a rule entering (`phase="breach"`) or
    leaving (`phase="clear"`) the breaching state.  Breaches carry the
    worst-N reqtrace exemplars captured at the transition tick."""

    __slots__ = ("ts", "phase", "rule", "metric", "kind", "op",
                 "threshold", "value", "margin", "exemplars")

    def __init__(self, ts, phase, rule, metric, kind, op, threshold,
                 value, margin, exemplars=None):
        self.ts = ts
        self.phase = phase
        self.rule = rule
        self.metric = metric
        self.kind = kind
        self.op = op
        self.threshold = threshold
        self.value = value
        self.margin = margin
        self.exemplars = exemplars

    def as_dict(self) -> dict:
        out = {"ts": round(self.ts, 6), "phase": self.phase,
               "rule": self.rule, "metric": self.metric, "op": self.op,
               "threshold": self.threshold,
               "value": round(self.value, 6),
               "margin": round(self.margin, 6)}
        if self.kind:
            out["kind"] = self.kind
        if self.exemplars:
            out["exemplars"] = self.exemplars
        return out


def validate_rules(obj) -> list[str]:
    """Schema check for an SLO rule-set object; returns a list of
    problems (empty == valid) — the contract `load_rules` enforces and
    tests/test_monitor.py pins."""
    problems: list[str] = []
    if not isinstance(obj, dict):
        return [f"slo rules are {type(obj).__name__}, not dict"]
    tick = obj.get("tick_s", 1.0)
    if not isinstance(tick, (int, float)) or isinstance(tick, bool) \
            or tick <= 0:
        problems.append(f"'tick_s' must be a positive number, "
                        f"got {tick!r}")
    rules = obj.get("rules")
    if not isinstance(rules, list) or not rules:
        return problems + ["'rules' must be a non-empty list"]
    names: set[str] = set()
    for i, r in enumerate(rules):
        where = f"rules[{i}]"
        if not isinstance(r, dict):
            problems.append(f"{where}: not a dict")
            continue
        metric = r.get("metric")
        if not (metric in SIGNALS
                or (isinstance(metric, str)
                    and metric.startswith("counter.")
                    and len(metric) > len("counter."))):
            problems.append(f"{where}: 'metric' must be one of "
                            f"{SIGNALS} or 'counter.<name>', got "
                            f"{metric!r}")
        kind = r.get("kind")
        if kind is not None:
            if not isinstance(kind, str) or not kind:
                problems.append(f"{where}: 'kind' must be a non-empty "
                                f"string, got {kind!r}")
            elif metric in SIGNALS and metric not in _KIND_SIGNALS:
                problems.append(f"{where}: metric {metric!r} does not "
                                f"take a kind label")
        if r.get("op") not in OPS:
            problems.append(f"{where}: 'op' must be one of {OPS}, got "
                            f"{r.get('op')!r}")
        thr = r.get("threshold")
        if not isinstance(thr, (int, float)) or isinstance(thr, bool):
            problems.append(f"{where}: 'threshold' must be a number, "
                            f"got {thr!r}")
        for field, lo in (("for", 1), ("clear", 1)):
            v = r.get(field, 1)
            if not isinstance(v, int) or isinstance(v, bool) or v < lo:
                problems.append(f"{where}: '{field}' must be an int "
                                f">= {lo}, got {v!r}")
        win = r.get("window_s", 10.0)
        if not isinstance(win, (int, float)) or isinstance(win, bool) \
                or win <= 0:
            problems.append(f"{where}: 'window_s' must be a positive "
                            f"number, got {win!r}")
        name = r.get("name")
        if name is not None and (not isinstance(name, str) or not name):
            problems.append(f"{where}: 'name' must be a non-empty "
                            f"string, got {name!r}")
        resolved = name or _default_name(metric, kind) \
            if isinstance(metric, str) else None
        if resolved:
            if resolved in names:
                problems.append(f"{where}: duplicate rule name "
                                f"{resolved!r}")
            names.add(resolved)
        unknown = set(r) - {"metric", "kind", "op", "threshold", "for",
                            "clear", "window_s", "name"}
        if unknown:
            problems.append(f"{where}: unknown field(s) "
                            f"{sorted(unknown)}")
    return problems


def _default_name(metric: str, kind) -> str:
    return f"{metric}@{kind}" if kind else metric


_SPEC_RE = re.compile(
    r"^(?P<metric>[a-z0-9_.]+)"
    r"(?:\{kind=(?P<kind>[a-z0-9_]+)\})?"
    r"\s*(?P<op><=|>=|<|>)\s*"
    r"(?P<thr>-?[0-9]+(?:\.[0-9]+)?)"
    r"(?P<opts>(?::[a-z_]+=[^:;]+)*)$")


def _parse_spec(text: str) -> dict:
    """Compact spec string -> rule-set dict (see module docstring)."""
    plan: dict = {"rules": []}
    for seg in text.split(";"):
        seg = seg.strip()
        if not seg:
            continue
        if seg.startswith("tick_s="):
            try:
                plan["tick_s"] = float(seg[len("tick_s="):])
            except ValueError:
                raise ValueError(f"slo spec: bad tick segment {seg!r}")
            continue
        m = _SPEC_RE.match(seg)
        if not m:
            raise ValueError(
                f"slo spec segment {seg!r} is not "
                f"metric[{{kind=k}}]<op>threshold[:opt=v...]")
        rule: dict = {"metric": m.group("metric"), "op": m.group("op"),
                      "threshold": float(m.group("thr"))}
        if m.group("kind"):
            rule["kind"] = m.group("kind")
        for opt in filter(None, (m.group("opts") or "").split(":")):
            k, _, v = opt.partition("=")
            if k in ("for", "clear"):
                try:
                    rule[k] = int(v)
                except ValueError:
                    raise ValueError(f"slo spec: {k}={v!r} not an int")
            elif k == "window_s":
                try:
                    rule[k] = float(v)
                except ValueError:
                    raise ValueError(f"slo spec: {k}={v!r} not a number")
            elif k == "name":
                rule[k] = v
            else:
                raise ValueError(f"slo spec: unknown option {k!r}")
        plan["rules"].append(rule)
    return plan


def load_rules(source) -> dict:
    """Build a validated rule-set dict from a dict, a JSON string, a
    JSON file path, or a compact spec string.  Raises ValueError (with
    every schema problem listed) — a pod round must not half-run a
    typo'd SLO set."""
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
        raise ValueError(f"cannot load slo rules from "
                         f"{type(source).__name__}")
    problems = validate_rules(obj)
    if problems:
        raise ValueError("invalid slo rules: " + "; ".join(problems))
    return obj


class _RuleState:
    __slots__ = ("name", "metric", "kind", "op", "threshold",
                 "for_ticks", "clear_ticks", "window_s", "breaching",
                 "bad_streak", "ok_streak", "breaches", "clears",
                 "worst_margin", "last_value", "ticks", "profiled",
                 "dumped")

    def __init__(self, r: dict):
        self.metric = r["metric"]
        self.kind = r.get("kind")
        self.name = r.get("name") or _default_name(self.metric,
                                                   self.kind)
        self.op = r["op"]
        self.threshold = float(r["threshold"])
        self.for_ticks = int(r.get("for", 1))
        self.clear_ticks = int(r.get("clear", 1))
        self.window_s = float(r.get("window_s", 10.0))
        self.breaching = False
        self.bad_streak = 0
        self.ok_streak = 0
        self.breaches = 0
        self.clears = 0
        self.worst_margin = None
        self.last_value = None
        self.ticks = 0
        self.profiled = False
        self.dumped = False

    def describe(self) -> dict:
        out = {"name": self.name, "metric": self.metric, "op": self.op,
               "threshold": self.threshold, "for": self.for_ticks,
               "clear": self.clear_ticks, "window_s": self.window_s}
        if self.kind:
            out["kind"] = self.kind
        return out


class Watchdog:
    """The rule evaluator.  `tick()` is the whole engine — the daemon
    thread just calls it on an interval, and tests drive it directly
    with a fake clock (`clock=` plus explicit `tick(now=...)`).  The
    signal providers are injectable for the same reason; defaults read
    the live registry."""

    def __init__(self, rules, tick_s: float | None = None,
                 clock=time.monotonic, status_provider=None,
                 summary_provider=None, counter_provider=None,
                 watermark_provider=None, profile_dir: str | None = None,
                 window: int = 2048):
        obj = load_rules(rules)
        self.rules = [_RuleState(r) for r in obj["rules"]]
        self.tick_s = float(tick_s if tick_s is not None
                            else obj.get("tick_s", 1.0))
        self._clock = clock
        self._status = status_provider or metrics_export.get_status
        self._summary = summary_provider or reqtrace.rolling_summary
        self._counters = counter_provider or core.counter_value
        self._watermarks = watermark_provider or costmodel.watermark_bytes
        self._profile_dir = profile_dir
        self._window = int(window)
        self._lock = threading.Lock()
        self._events: list[SloBreach] = []
        self._events_dropped = 0
        self._ticks = 0
        self._profiles: list[str] = []
        self._profile_until: float | None = None
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        # rolling histories for the rate/slope/flap signals
        self._tp_hist: collections.deque = collections.deque(
            maxlen=_HIST_LEN)           # (ts, total, by_kind)
        self._ctr_hist: dict[str, collections.deque] = {
            r.metric[len("counter."):]: collections.deque(maxlen=_HIST_LEN)
            for r in self.rules if r.metric.startswith("counter.")}
        self._breaker_prev: dict | None = None
        self._flap_hist: collections.deque = collections.deque(
            maxlen=_HIST_LEN)           # (ts, transitions)
        self._wm_hist: dict[str, collections.deque] = {}
        self._incidents: list[str] = []
        self._occ_prev: float | None = None

    # --- the tick ------------------------------------------------------------

    def tick(self, now: float | None = None) -> list[SloBreach]:
        """Evaluate every rule once; returns the transitions this tick
        emitted (breaches and clears)."""
        now = self._clock() if now is None else now
        self._maybe_stop_profile(now)
        frame = self._frame(now)
        emitted: list[SloBreach] = []
        for st in self.rules:
            value = self._signal(st, frame, now)
            st.ticks += 1
            if value is None:
                continue        # no observation: streaks hold
            st.last_value = float(value)
            healthy = _OP_FNS[st.op](value, st.threshold)
            margin = _MARGINS[st.op](value, st.threshold)
            if not healthy:
                st.bad_streak += 1
                st.ok_streak = 0
                if st.worst_margin is None or margin > st.worst_margin:
                    st.worst_margin = margin
                if not st.breaching and st.bad_streak >= st.for_ticks:
                    st.breaching = True
                    st.breaches += 1
                    ev = self._emit(now, "breach", st, value, margin,
                                    exemplars=self._exemplars())
                    emitted.append(ev)
                    self._maybe_profile(st, now)
                    self._maybe_flightrec(st)
            else:
                st.ok_streak += 1
                st.bad_streak = 0
                if st.breaching and st.ok_streak >= st.clear_ticks:
                    st.breaching = False
                    st.clears += 1
                    emitted.append(self._emit(now, "clear", st, value,
                                              margin))
        with self._lock:
            self._ticks += 1
        core.count("slo.ticks")
        return emitted

    def _emit(self, now, phase, st, value, margin,
              exemplars=None) -> SloBreach:
        ev = SloBreach(now, phase, st.name, st.metric, st.kind, st.op,
                       st.threshold, float(value), float(margin),
                       exemplars)
        with self._lock:
            if len(self._events) < _MAX_EVENTS:
                self._events.append(ev)
            else:
                self._events_dropped += 1
        core.count("slo.breaches" if phase == "breach" else "slo.clears")
        core.count(f"slo.{phase}.{st.name}")
        flightrec.record(f"slo_{phase}", rule=st.name, metric=st.metric,
                         value=round(float(value), 6),
                         threshold=st.threshold,
                         margin=round(float(margin), 6))
        return ev

    def _exemplars(self, n: int = 5) -> list[dict]:
        try:
            return reqtrace.attribution(worst_n=n)["worst"]
        except Exception:
            return []

    # --- signal resolution ---------------------------------------------------

    def _frame(self, now: float) -> dict:
        """One tick's shared signal reads (each live surface is read at
        most once per tick, whatever the rule count)."""
        frame: dict = {"summary": None, "status": None}
        if any(r.metric in _KIND_SIGNALS for r in self.rules):
            try:
                frame["summary"] = self._summary(self._window)
            except TypeError:
                frame["summary"] = self._summary()
            except Exception:
                frame["summary"] = None
        if any(r.metric.startswith(("serve.queue", "serve.inflight",
                                    "breaker.")) for r in self.rules):
            frame["status"] = self._status()
        # throughput history
        if any(r.metric == "serve.throughput_rps" for r in self.rules):
            total, by_kind, _ = reqtrace.completed_totals()
            self._tp_hist.append((now, total, dict(by_kind)))
        for cname, hist in self._ctr_hist.items():
            hist.append((now, self._counters(cname)))
        if any(r.metric == "breaker.flaps" for r in self.rules):
            self._note_flaps(frame.get("status"), now)
        if any(r.metric == "mem.slope_mb_s" for r in self.rules):
            try:
                for dev, last in (self._watermarks() or {}).items():
                    self._wm_hist.setdefault(
                        dev, collections.deque(maxlen=_HIST_LEN)
                    ).append((now, last))
            except Exception:
                pass
        return frame

    def _note_flaps(self, status, now: float) -> None:
        breakers = (status or {}).get("breakers") or {}
        states = {k: (b.get("state") if isinstance(b, dict) else b)
                  for k, b in breakers.items()}
        flips = 0
        if self._breaker_prev is not None:
            for k, s in states.items():
                if self._breaker_prev.get(k, s) != s:
                    flips += 1
        self._breaker_prev = states
        self._flap_hist.append((now, flips))

    def _signal(self, st: _RuleState, frame: dict, now: float):
        m = st.metric
        if m in ("serve.p50_ms", "serve.p99_ms"):
            summary = frame.get("summary") or {}
            key = "p50_ms" if m == "serve.p50_ms" else "p99_ms"
            if st.kind:
                s = summary.get(st.kind)
                return s[key] if s else None
            vals = [s[key] for s in summary.values()]
            return max(vals) if vals else None
        if m == "serve.throughput_rps":
            return self._rate(self._tp_hist, st, now,
                              lambda e: (e[2].get(st.kind, 0)
                                         if st.kind else e[1]))
        if m.startswith("counter."):
            hist = self._ctr_hist.get(m[len("counter."):])
            return self._rate(hist, st, now, lambda e: e[1])
        if m == "serve.queue_depth":
            status = frame.get("status")
            return None if status is None \
                else status.get("queue", {}).get("depth", 0)
        if m == "serve.queue_age_s":
            status = frame.get("status")
            if status is None:
                return None
            return status.get("queue", {}).get("oldest_age_s") or 0.0
        if m == "serve.inflight_batches":
            status = frame.get("status")
            return None if status is None \
                else status.get("inflight", {}).get("batches", 0)
        if m == "breaker.flaps":
            cut = now - st.window_s
            return float(sum(n for ts, n in self._flap_hist if ts > cut))
        if m == "serve.busy_frac":
            value = occupancy.live_busy_frac(st.window_s)
            # occupancy-collapse edge: a pipeline that WAS keeping the
            # device busy falling off a cliff is flight-recorder news
            # even before the rule's `for=` streak confirms the breach
            if value is not None:
                prev, self._occ_prev = self._occ_prev, value
                if prev is not None and prev >= 0.2 and value < 0.05:
                    flightrec.record("occupancy_collapse",
                                     prev=round(prev, 6),
                                     value=round(value, 6),
                                     rule=st.name)
            return value
        if m == "mem.slope_mb_s":
            slopes = []
            for hist in self._wm_hist.values():
                base = None
                for ts, b in hist:
                    if ts >= now - st.window_s:
                        base = (ts, b)
                        break
                if base is None or not hist:
                    continue
                t1, b1 = hist[-1]
                if t1 - base[0] <= 0:
                    continue
                slopes.append((b1 - base[1]) / (t1 - base[0]) / 1e6)
            return max(slopes) if slopes else None
        return None

    @staticmethod
    def _rate(hist, st: _RuleState, now: float, get):
        """Rate/s of a monotone total over the rule's window: current
        sample vs the oldest sample inside the window.  None until two
        samples exist (a rate needs a baseline)."""
        if not hist or len(hist) < 2:
            return None
        base = None
        for entry in hist:
            if entry[0] >= now - st.window_s:
                base = entry
                break
        if base is None or base is hist[-1]:
            base = hist[-2]
        dt = hist[-1][0] - base[0]
        if dt <= 0:
            return None
        return (get(hist[-1]) - get(base)) / dt

    # --- breach profiler grab ------------------------------------------------

    def _maybe_profile(self, st: _RuleState, now: float) -> None:
        if not self._profile_dir or st.profiled \
                or self._profile_until is not None:
            return
        jax = sys.modules.get("jax")
        if jax is None:
            return
        path = os.path.join(self._profile_dir, st.name)
        try:
            os.makedirs(path, exist_ok=True)
            jax.profiler.start_trace(path)
        except Exception:
            core.count("slo.profile_failed")
            return
        st.profiled = True
        self._profile_until = now + _PROFILE_GRAB_S
        self._profiles.append(path)
        core.count("slo.profiles")

    def _maybe_flightrec(self, st: _RuleState) -> None:
        """Breach-triggered incident dump (CST_FLIGHTREC_ON_BREACH) —
        once per rule per watchdog install, the same gating discipline
        as the CST_PROFILE_ON_BREACH grab: the first breach is the
        incident, repeats are the same incident still happening."""
        if st.dumped or not flightrec.dump_on_breach():
            return
        st.dumped = True
        try:
            path = flightrec.dump_bundle(reason=f"slo-{st.name}",
                                         rule=st.name)
        except Exception:
            core.count("slo.incident_dump_failed")
            return
        self._incidents.append(path)
        core.count("slo.incident_bundles")

    def _maybe_stop_profile(self, now: float) -> None:
        if self._profile_until is None or now < self._profile_until:
            return
        self._profile_until = None
        jax = sys.modules.get("jax")
        if jax is not None:
            try:
                jax.profiler.stop_trace()
            except Exception:
                core.count("slo.profile_failed")

    # --- daemon loop ---------------------------------------------------------

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        t = threading.Thread(target=self._loop, name="cst-slo-watchdog",
                             daemon=True)
        self._thread = t
        t.start()

    def _loop(self) -> None:
        while not self._stop.wait(self.tick_s):
            try:
                self.tick()
            except Exception:
                core.count("slo.tick_error")

    def stop(self) -> None:
        self._stop.set()
        t, self._thread = self._thread, None
        if t is not None:
            t.join(timeout=5.0)
        # never leave a profiler trace open past the round
        self._maybe_stop_profile(float("inf"))

    # --- read surfaces -------------------------------------------------------

    def events(self) -> list[dict]:
        with self._lock:
            return [e.as_dict() for e in self._events]

    def breaching(self) -> list[str]:
        """Names of the rules currently in breach."""
        return [st.name for st in self.rules if st.breaching]

    def slo_block(self) -> dict:
        """The round-summary sub-object (rides the serve and
        resilience blocks as `"slo"`)."""
        with self._lock:
            events = [e.as_dict() for e in self._events]
            dropped = self._events_dropped
            ticks = self._ticks
        rules = []
        for st in self.rules:
            row = st.describe()
            row.update({"ticks": st.ticks, "breaches": st.breaches,
                        "clears": st.clears, "breaching": st.breaching})
            if st.worst_margin is not None:
                row["worst_margin"] = round(st.worst_margin, 6)
            if st.last_value is not None:
                row["last_value"] = round(st.last_value, 6)
            rules.append(row)
        # bound the block: only the LAST 5 breaches keep their exemplar
        # payloads (the freshest evidence), older events keep the
        # transition facts only
        breach_idx = [i for i, e in enumerate(events)
                      if e["phase"] == "breach"]
        keep = set(breach_idx[-5:])
        bounded = []
        for i, e in enumerate(events):
            if "exemplars" in e and i not in keep:
                e = {k: v for k, v in e.items() if k != "exemplars"}
            bounded.append(e)
        total = sum(st.breaches for st in self.rules)
        return {"ticks": ticks, "breaches": total,
                "clean": total == 0,
                "breaching_now": self.breaching(),
                "rules": rules,
                "events": bounded,
                "events_dropped": dropped,
                "profiles": list(self._profiles),
                "incidents": list(self._incidents)}

    def exposition_rows(self):
        """Metric families for the exposition endpoint:
        (name, type, help, [(labels, value), ...])."""
        labels = [({"rule": st.name}, st) for st in self.rules]
        return [
            ("cst_slo_breaches_total", "counter",
             "SLO breach transitions per rule",
             [(lb, st.breaches) for lb, st in labels]),
            ("cst_slo_breaching", "gauge",
             "1 while the rule is in breach",
             [(lb, 1 if st.breaching else 0) for lb, st in labels]),
            ("cst_slo_last_value", "gauge",
             "last evaluated signal value per rule",
             [(lb, st.last_value) for lb, st in labels
              if st.last_value is not None]),
            ("cst_slo_ticks_total", "counter",
             "watchdog evaluation ticks", [({}, self._ticks)]),
        ]


# --- the gate (the faults `active()` pattern) --------------------------------

_watchdog: Watchdog | None = None


def active() -> bool:
    """True while a watchdog is installed — one module-global read."""
    return _watchdog is not None


def current() -> Watchdog | None:
    return _watchdog


def install(rules, *, autostart: bool = True, **kwargs) -> Watchdog:
    """Build, install and (by default) start a watchdog over `rules`
    (any `load_rules` source form).  Replaces a previous watchdog
    (stopping its thread)."""
    global _watchdog
    if _watchdog is not None:
        _watchdog.stop()
    wd = Watchdog(rules, **kwargs)
    _watchdog = wd
    if autostart:
        wd.start()
    return wd


def clear() -> dict | None:
    """Stop and uninstall the watchdog; returns its final `slo_block()`
    (the round-summary evidence), or None when none was installed."""
    global _watchdog
    wd, _watchdog = _watchdog, None
    if wd is None:
        return None
    wd.stop()
    return wd.slo_block()


def profile_dir_from_env() -> str | None:
    """The `CST_PROFILE_ON_BREACH` capture directory: unset/"0" = off,
    "1" = the default `out/slo_profiles`, anything else is the path."""
    raw = os.environ.get("CST_PROFILE_ON_BREACH", "")
    if raw in ("", "0"):
        return None
    return "out/slo_profiles" if raw == "1" else raw


def install_from_env(status_provider=None,
                     autostart: bool = True) -> Watchdog | None:
    """Install the `CST_SLO_RULES` watchdog when the knob is set.  A
    malformed rule set is rejected with a counted warning
    (`slo.rules_invalid`) instead of an exception — a typo'd knob must
    not kill a serve round.  Also starts the `CST_METRICS_PORT`
    exposition endpoint (the two arm together on the pod checklist).
    Call sites: loadgen and the chaos harness — never at import."""
    metrics_export.start_from_env()
    if status_provider is not None:
        metrics_export.set_status_provider(status_provider)
    source = os.environ.get("CST_SLO_RULES")
    if not source:
        return _watchdog
    try:
        rules = load_rules(source)
    except (ValueError, json.JSONDecodeError) as exc:
        core.count("slo.rules_invalid")
        print(f"slo: ignoring invalid CST_SLO_RULES: {exc}",
              file=sys.stderr)
        return None
    return install(rules, autostart=autostart,
                   profile_dir=profile_dir_from_env())


def _reset_state() -> None:
    """Full test-isolation reset (telemetry.reset(full=True) hook)."""
    global _watchdog
    wd, _watchdog = _watchdog, None
    if wd is not None:
        wd.stop()
