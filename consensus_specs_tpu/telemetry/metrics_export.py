"""Prometheus text-format exposition of the live telemetry registry.

Every observability layer before this one ends at an artifact read
AFTER a round finishes (the serve block, Chrome traces).
This module is the live surface: a zero-dependency HTTP endpoint
(stdlib `http.server`, daemon thread, armed by `CST_METRICS_PORT`)
rendering the whole registry in the Prometheus text exposition format
(version 0.0.4) on every scrape — counters, gauges, histogram and span
summaries, per-device memory watermarks from the cost model, the
request-trace rolling window (per-kind p50/p99 quantiles + lifetime
outcome totals), the serve executor's queue/in-flight/breaker state
(via a registered status provider), and the SLO watchdog's breach
counters (`monitor.py`).

Naming contract: registry names are dotted (`serve.submitted`,
`kernel.run_s`); exposition names are the `cst_`-prefixed sanitized
form (`.` and every other non-metric character -> `_`), so
`serve.submitted` scrapes as `cst_serve_submitted_total`.  Sanitization
must be collision-free — two registry names that sanitize to the same
exposition name would silently merge series, so collisions are dropped
and counted (`metrics.name_collision`), and the analyzer rule
`metric-name-invalid` makes the source-level invariant a lint check.

`render_exposition()` is pure (registry snapshot -> text) and
`parse_exposition()` is its validating inverse, which the round-trip
test goes through.

Gating contract (the telemetry pattern): the server only starts when
`CST_METRICS_PORT` is set (or `start()` is called explicitly); nothing
here runs on any hot path — cost is paid per scrape, by the scraper's
request thread.  Stdlib-only; never imports jax or numpy (same
discipline as the rest of `telemetry/`).
"""

from __future__ import annotations

import os
import re
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from . import core, costmodel, occupancy, reqtrace

CONTENT_TYPE = "text/plain; version=0.0.4; charset=utf-8"

# the Prometheus data-model charsets (exposition-format spec)
METRIC_NAME_RE = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*\Z")
LABEL_NAME_RE = re.compile(r"[a-zA-Z_][a-zA-Z0-9_]*\Z")

_SANITIZE_RE = re.compile(r"[^a-zA-Z0-9_:]")

_lock = threading.Lock()
_server: ThreadingHTTPServer | None = None
_thread: threading.Thread | None = None
_status_provider = None     # callable -> ServeExecutor.status()-shaped dict


def sanitize_name(name: str) -> str:
    """Registry name -> exposition metric-name stem: every character
    outside the metric charset (dots, `@`, dashes) becomes `_`, and a
    leading digit gets a `_` prefix.  The `cst_` family prefix is added
    by the renderer."""
    out = _SANITIZE_RE.sub("_", name)
    if out and out[0].isdigit():
        out = "_" + out
    return out


def _escape_label(value: str) -> str:
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _fmt(value) -> str:
    v = float(value)
    if v != v:
        return "NaN"
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return repr(v)


class _Lines:
    """Exposition builder: tracks emitted metric names so a sanitization
    collision (two registry names -> one exposition name) is dropped and
    counted instead of silently merging series."""

    def __init__(self):
        self.out: list[str] = []
        self._typed: dict[str, str] = {}
        self.collisions = 0

    def family(self, name: str, mtype: str, help_text: str) -> bool:
        prev = self._typed.get(name)
        if prev is not None:
            if prev != mtype:
                self.collisions += 1
                return False
            return True
        if not METRIC_NAME_RE.match(name):
            self.collisions += 1
            return False
        self._typed[name] = mtype
        self.out.append(f"# HELP {name} {help_text}")
        self.out.append(f"# TYPE {name} {mtype}")
        return True

    def sample(self, name: str, value, labels: dict | None = None) -> None:
        if labels:
            body = ",".join(f'{k}="{_escape_label(v)}"'
                            for k, v in sorted(labels.items()))
            self.out.append(f"{name}{{{body}}} {_fmt(value)}")
        else:
            self.out.append(f"{name} {_fmt(value)}")


def set_status_provider(fn) -> None:
    """Register the live serve-status callable (`ServeExecutor.status`)
    so scrapes — and the SLO watchdog — see queue depth, in-flight
    counts and breaker states.  Pass None to unregister (executor
    close)."""
    global _status_provider
    _status_provider = fn


def get_status() -> dict | None:
    """The registered provider's current status dict, or None (no
    provider / provider raised — a dying executor must not kill a
    scrape)."""
    fn = _status_provider
    if fn is None:
        return None
    try:
        return fn()
    except Exception:
        core.count("metrics.status_provider_error")
        return None


_BREAKER_STATES = {"closed": 0, "half_open": 1, "half-open": 1, "open": 2}


def render_exposition(snap: dict | None = None,
                      status: dict | None = None) -> str:
    """The whole registry as Prometheus exposition text.  Deterministic
    given the snapshot (sorted families, sorted labels) so tests can pin
    the format line-by-line."""
    if snap is None:
        snap = core.snapshot()
    if status is None:
        status = get_status()
    L = _Lines()

    L.family("cst_telemetry_enabled", "gauge",
             "1 while the telemetry registry is collecting")
    L.sample("cst_telemetry_enabled", 1 if snap.get("enabled") else 0)

    for name, v in sorted(snap.get("counters", {}).items()):
        m = f"cst_{sanitize_name(name)}_total"
        if L.family(m, "counter", f"telemetry counter {name}"):
            L.sample(m, v)
    for name, g in sorted(snap.get("gauges", {}).items()):
        m = f"cst_{sanitize_name(name)}"
        if L.family(m, "gauge", f"telemetry gauge {name} (last sample)"):
            L.sample(m, g["last"])
    for name, h in sorted(snap.get("histograms", {}).items()):
        stem = f"cst_{sanitize_name(name)}"
        if L.family(stem, "summary", f"telemetry histogram {name}"):
            L.sample(f"{stem}_count", h["count"])
            L.sample(f"{stem}_sum", h["total"])
            L.sample(f"{stem}_min", h["min"])
            L.sample(f"{stem}_max", h["max"])
    for name, s in sorted(snap.get("spans", {}).items()):
        stem = f"cst_{sanitize_name(name)}_seconds"
        if L.family(stem, "summary", f"telemetry span {name}"):
            L.sample(f"{stem}_count", s["count"])
            L.sample(f"{stem}_sum", s["total_s"])
            L.sample(f"{stem}_min", s["min_s"])
            L.sample(f"{stem}_max", s["max_s"])

    # per-device memory watermarks (cost model)
    wms = snap.get("costmodel", {}).get("watermarks", {})
    if wms:
        L.family("cst_device_memory_bytes", "gauge",
                 "live device buffer bytes (last watermark sample)")
        for dev, wm in sorted(wms.items()):
            L.sample("cst_device_memory_bytes", wm["last_bytes"],
                     {"device": dev})
        L.family("cst_device_memory_high_water_bytes", "gauge",
                 "device buffer high-water mark")
        for dev, wm in sorted(wms.items()):
            L.sample("cst_device_memory_high_water_bytes",
                     wm["high_water_bytes"], {"device": dev})

    # request tracing: rolling-window quantiles + lifetime totals
    rolling = reqtrace.rolling_summary()
    if rolling:
        L.family("cst_serve_request_latency_ms", "summary",
                 "per-kind rolling-window request latency quantiles")
        for kind, s in sorted(rolling.items()):
            for q, key in (("0.5", "p50_ms"), ("0.99", "p99_ms")):
                L.sample("cst_serve_request_latency_ms", s[key],
                         {"kind": kind, "quantile": q})
        L.family("cst_serve_request_window_count", "gauge",
                 "answered requests in the rolling summary window")
        for kind, s in sorted(rolling.items()):
            L.sample("cst_serve_request_window_count", s["count"],
                     {"kind": kind})
    total, by_kind, by_outcome = reqtrace.completed_totals()
    if total:
        L.family("cst_serve_requests_total", "counter",
                 "completed requests by kind (process lifetime)")
        for kind, n in sorted(by_kind.items()):
            L.sample("cst_serve_requests_total", n, {"kind": kind})
        L.family("cst_serve_outcomes_total", "counter",
                 "completed requests by outcome (process lifetime)")
        for outcome, n in sorted(by_outcome.items()):
            L.sample("cst_serve_outcomes_total", n, {"outcome": outcome})

    if status:
        # `cst_serve_live_*`: read from ServeExecutor.status() at scrape
        # time — the `cst_serve_queue_depth`-style names stay reserved
        # for the registry's own sampled gauges (same source, different
        # timing), so the two surfaces never collide
        queue = status.get("queue", {})
        L.family("cst_serve_live_queue_depth", "gauge",
                 "serve executor queued requests (at scrape)")
        L.sample("cst_serve_live_queue_depth", queue.get("depth", 0))
        L.family("cst_serve_live_queue_oldest_age_seconds", "gauge",
                 "age of the oldest queued request (at scrape)")
        L.sample("cst_serve_live_queue_oldest_age_seconds",
                 queue.get("oldest_age_s") or 0.0)
        by_kind_q = queue.get("by_kind") or {}
        if by_kind_q:
            L.family("cst_serve_live_queue_by_kind", "gauge",
                     "serve executor queued requests by kind (at scrape)")
            for kind, n in sorted(by_kind_q.items()):
                L.sample("cst_serve_live_queue_by_kind", n,
                         {"kind": kind})
        inflight = status.get("inflight", {})
        L.family("cst_serve_live_inflight_batches", "gauge",
                 "serve executor batches in flight (at scrape)")
        L.sample("cst_serve_live_inflight_batches",
                 inflight.get("batches", 0))
        L.family("cst_serve_live_inflight_requests", "gauge",
                 "serve executor requests in flight (at scrape)")
        L.sample("cst_serve_live_inflight_requests",
                 inflight.get("requests", 0))
        ctrs = status.get("counters") or {}
        if ctrs:
            L.family("cst_serve_executor_events_total", "counter",
                     "serve executor lifecycle counters")
            for key, n in sorted(ctrs.items()):
                L.sample("cst_serve_executor_events_total", n,
                         {"event": key})
        breakers = status.get("breakers") or {}
        if breakers:
            L.family("cst_serve_breaker_state", "gauge",
                     "circuit breaker state (0=closed 1=half-open 2=open)")
            for key, b in sorted(breakers.items()):
                state = b.get("state") if isinstance(b, dict) else b
                L.sample("cst_serve_breaker_state",
                         _BREAKER_STATES.get(str(state), 0), {"key": key})

    # device occupancy (CST_OCCUPANCY): live busy fraction per device +
    # cumulative bubble attribution over the ledger's extent
    occ = occupancy.live_summary()
    if occ is not None:
        L.family("cst_serve_device_busy_frac", "gauge",
                 "device busy fraction from the occupancy ledger "
                 "(at scrape)")
        L.sample("cst_serve_device_busy_frac", occ["busy_frac"])
        for dev, frac in sorted((occ.get("devices") or {}).items()):
            L.sample("cst_serve_device_busy_frac", frac,
                     {"device": dev})
        L.family("cst_serve_bubble_seconds_total", "counter",
                 "idle device wall attributed per pipeline-bubble "
                 "cause")
        for cause, v in sorted(occ["bubbles_s"].items()):
            L.sample("cst_serve_bubble_seconds_total", v,
                     {"cause": cause})

    # SLO watchdog (lazy import: monitor imports this module)
    from . import monitor
    wd = monitor.current()
    if wd is not None:
        for name, mtype, help_text, rows in wd.exposition_rows():
            if L.family(name, mtype, help_text):
                for labels, value in rows:
                    L.sample(name, value, labels)

    if L.collisions:
        core.count("metrics.name_collision", L.collisions)
        L.family("cst_metrics_name_collisions_total", "counter",
                 "registry names dropped from exposition (sanitization "
                 "collision)")
        L.sample("cst_metrics_name_collisions_total", L.collisions)
    return "\n".join(L.out) + "\n"


# --- the validating inverse --------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>[^}]*)\})?"
    r" (?P<value>-?(?:[0-9]+(?:\.[0-9]+)?(?:[eE][-+]?[0-9]+)?"
    r"|NaN|[+-]?Inf))$")
_LABEL_PAIR_RE = re.compile(
    r'^(?P<k>[a-zA-Z_][a-zA-Z0-9_]*)="(?P<v>(?:[^"\\]|\\.)*)"$')


def parse_exposition(text: str) -> dict:
    """Parse (and strictly validate) exposition text, returning
    `{metric_name: [(labels_dict, value), ...]}`.  Raises ValueError
    naming the first malformed line — the line-by-line format check of
    the round-trip test."""
    out: dict[str, list] = {}
    typed: set[str] = set()
    for lineno, line in enumerate(text.splitlines(), 1):
        if not line.strip():
            continue
        if line.startswith("#"):
            parts = line.split(None, 3)
            if len(parts) < 3 or parts[1] not in ("HELP", "TYPE"):
                raise ValueError(f"line {lineno}: malformed comment "
                                 f"{line!r}")
            if not METRIC_NAME_RE.match(parts[2]):
                raise ValueError(f"line {lineno}: invalid metric name "
                                 f"{parts[2]!r}")
            if parts[1] == "TYPE":
                if parts[2] in typed:
                    raise ValueError(f"line {lineno}: duplicate TYPE "
                                     f"for {parts[2]!r}")
                typed.add(parts[2])
            continue
        m = _SAMPLE_RE.match(line)
        if not m:
            raise ValueError(f"line {lineno}: malformed sample {line!r}")
        labels = {}
        body = m.group("labels")
        if body:
            for pair in _split_label_pairs(body, lineno):
                pm = _LABEL_PAIR_RE.match(pair)
                if not pm:
                    raise ValueError(f"line {lineno}: malformed label "
                                     f"pair {pair!r}")
                labels[pm.group("k")] = pm.group("v")
        out.setdefault(m.group("name"), []).append(
            (labels, float(m.group("value"))))
    return out


def _split_label_pairs(body: str, lineno: int) -> list[str]:
    """Split `k="v",k2="v2"` on commas outside quotes."""
    pairs, cur, in_q, esc = [], [], False, False
    for ch in body:
        if esc:
            cur.append(ch)
            esc = False
        elif ch == "\\" and in_q:
            cur.append(ch)
            esc = True
        elif ch == '"':
            cur.append(ch)
            in_q = not in_q
        elif ch == "," and not in_q:
            pairs.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if in_q:
        raise ValueError(f"line {lineno}: unterminated label quote")
    if cur:
        pairs.append("".join(cur))
    return pairs


# --- the endpoint ------------------------------------------------------------


class _MetricsHandler(BaseHTTPRequestHandler):
    def do_GET(self):          # noqa: N802 (http.server API)
        if self.path.split("?")[0] not in ("/", "/metrics"):
            self.send_error(404)
            return
        try:
            body = render_exposition().encode("utf-8")
        except Exception as exc:   # a scrape must never crash the server
            core.count("metrics.render_error")
            self.send_error(500, explain=str(exc))
            return
        core.count("metrics.scrapes")
        self.send_response(200)
        self.send_header("Content-Type", CONTENT_TYPE)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *args):   # silence per-request stderr noise
        pass


def start(port: int | None = None) -> int:
    """Start the exposition endpoint on `port` (0 = ephemeral; default
    from CST_METRICS_PORT) and return the bound port.  Idempotent — a
    second start returns the running server's port."""
    global _server, _thread
    with _lock:
        if _server is not None:
            return _server.server_address[1]
        if port is None:
            port = int(os.environ.get("CST_METRICS_PORT", "0") or "0")
        srv = ThreadingHTTPServer(("127.0.0.1", port), _MetricsHandler)
        srv.daemon_threads = True
        t = threading.Thread(target=srv.serve_forever, name="cst-metrics",
                             daemon=True)
        t.start()
        _server, _thread = srv, t
        bound = srv.server_address[1]
    core.set_meta("metrics_port", bound)
    return bound


def stop() -> None:
    global _server, _thread
    with _lock:
        srv, _server, _thread = _server, None, None
    if srv is not None:
        srv.shutdown()
        srv.server_close()


def serving_port() -> int | None:
    """The live endpoint's port, or None while stopped."""
    srv = _server
    return srv.server_address[1] if srv is not None else None


def start_from_env() -> int | None:
    """Start the endpoint when `CST_METRICS_PORT` is set (non-"0");
    returns the bound port or None.  Call sites: loadgen and the chaos
    harness — never at import."""
    raw = os.environ.get("CST_METRICS_PORT", "")
    if raw in ("", "0"):
        return serving_port()
    return start(int(raw))


def _reset_state() -> None:
    """Full test-isolation reset (telemetry.reset(full=True) hook):
    stop the server and drop the status provider."""
    global _status_provider
    stop()
    _status_provider = None
