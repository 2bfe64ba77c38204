"""Telemetry exporters (JSON-lines, Chrome trace-event) and the schema
checks of the blocks the serve and chaos rounds return.

Two consumers, two formats:

- `write_jsonl(path)`     one JSON object per line (spans as emitted),
                          greppable / `jq`-able post-hoc.
- `write_chrome_trace(path)`  the Trace Event Format JSON object
                          (https://docs.google.com/document/d/1CvAClvFfyA5R-PhYUmn5OOQtYMH4h6I0nSsKchNAySU)
                          loadable in Perfetto / chrome://tracing.
                          Armed automatically at process exit when
                          `CST_TRACE_FILE` is set.
"""

from __future__ import annotations

import json
import os

from . import core


def write_jsonl(path: str) -> int:
    """Write every buffered span event as one JSON line; returns the
    number of lines written."""
    events, _ = core._events_copy()
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    return len(events)


def chrome_trace() -> dict:
    """The trace-event JSON object: buffered spans as 'X' (complete)
    events; cost-model watermark samples, per-kernel cost records, and
    gauge samples (serve queue depth / in-flight batches) as 'C'
    (counter) events — so the Perfetto timeline shows device-memory
    pressure, kernel flop/byte budgets, and the serve pipeline's
    breathing alongside the span track — plus process/thread metadata,
    all on one pid."""
    from . import costmodel

    events, dropped = core._events_copy()
    pid = os.getpid()
    out = [{
        "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
        "args": {"name": "consensus_specs_tpu"},
    }]
    for e in events:
        out.append({
            "name": e["name"], "ph": "X", "cat": "cst",
            "pid": pid, "tid": e["tid"],
            "ts": round(e["ts"], 3), "dur": round(e["dur"], 3),
            "args": e["args"],
        })
    wm_events, wm_dropped = costmodel._wm_events_copy()
    for w in wm_events:
        # one counter series per device: Perfetto renders each args key
        # as its own track under the "device_memory_bytes" counter
        out.append({
            "name": "device_memory_bytes", "ph": "C", "cat": "cst",
            "pid": pid, "tid": 0, "ts": round(w["ts"], 3),
            "args": {dev: b for dev, b in w["bytes"].items()},
        })
    for c in costmodel._cost_events_copy():
        if "error" in c:
            continue
        out.append({
            "name": f"cost.{c['kernel']}", "ph": "C", "cat": "cst",
            "pid": pid, "tid": 0,
            "ts": round(c.get("ts_rel_us", 0.0), 3),
            "args": {"flops": c.get("flops", 0.0),
                     "bytes_accessed": c.get("bytes_accessed", 0.0)},
        })
    gauge_events, g_dropped = core._gauge_events_copy()
    for g in gauge_events:
        # one counter track per gauge name (serve.queue_depth,
        # serve.inflight_batches, ...) next to device_memory_bytes
        out.append({
            "name": g["name"], "ph": "C", "cat": "cst",
            "pid": pid, "tid": 0, "ts": round(g["ts"], 3),
            "args": {"value": g["value"]},
        })
    # request tracing (CST_TRACE_REQUESTS): per-request lifecycle 'X'
    # spans + 's'/'t'/'f' flow arrows (submit → batch → settle) + batch
    # spans, on per-kind request tracks next to the span timeline
    from . import reqtrace

    out.extend(reqtrace.chrome_events(pid, core._T0))
    # device-occupancy busy tracks (CST_OCCUPANCY): one 'C' counter per
    # device rising to 1 over each merged busy span, so pipeline
    # bubbles are visible as flat-zero stretches next to the request
    # and gauge tracks
    from . import occupancy

    out.extend(occupancy.chrome_events(pid, core._T0))
    trace = {"traceEvents": out, "displayTimeUnit": "ms"}
    if dropped or wm_dropped or g_dropped:
        trace["otherData"] = {
            "events_dropped": dropped + wm_dropped + g_dropped}
    return trace


def write_chrome_trace(path: str) -> None:
    # serialize fully before touching the file, and never raise:
    # exporting must not fail (or truncate) at process exit — but a
    # skipped export is announced, not silent, the file IS the output
    try:
        data = json.dumps(chrome_trace())
        dirname = os.path.dirname(path)
        if dirname:
            os.makedirs(dirname, exist_ok=True)
        with open(path, "w") as f:
            f.write(data)
    except Exception as e:
        import sys

        print(f"telemetry: chrome trace not written to {path}: {e}",
              file=sys.stderr)


_BOUNDS = ("compute", "memory", "launch", "unknown")


def validate_costmodel_block(cm) -> list[str]:
    """Schema check for the `"costmodel"` sub-object (CST_COSTMODEL
    rounds); returns problems (empty == valid).  Error records (capture
    failed, reason attached) are valid by design — a kernel the backend
    cannot analyze must stay visible, not break the contract."""
    problems: list[str] = []
    if not isinstance(cm, dict):
        return [f"costmodel block is {type(cm).__name__}, not dict"]
    kernels = cm.get("kernels")
    if not isinstance(kernels, dict):
        problems.append("costmodel['kernels'] must be a dict")
        kernels = {}
    for name, rec in kernels.items():
        if not isinstance(rec, dict):
            problems.append(f"costmodel kernel {name!r} must be a dict")
            continue
        if "error" in rec:
            continue
        for key in ("flops", "bytes_accessed"):
            v = rec.get(key)
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or v < 0:
                problems.append(f"costmodel kernel {name!r}: {key!r} "
                                f"must be a non-negative number, got {v!r}")
        if rec.get("bound") not in _BOUNDS:
            problems.append(f"costmodel kernel {name!r}: 'bound' must "
                            f"be one of {_BOUNDS}, got {rec.get('bound')!r}")
    wms = cm.get("watermarks")
    if not isinstance(wms, dict):
        problems.append("costmodel['watermarks'] must be a dict")
        wms = {}
    for dev, wm in wms.items():
        if not isinstance(wm, dict) or not isinstance(
                wm.get("high_water_bytes"), int):
            problems.append(f"costmodel watermark {dev!r} must carry an "
                            f"int 'high_water_bytes'")
        elif isinstance(wm.get("last_bytes"), int) \
                and wm["last_bytes"] > wm["high_water_bytes"]:
            problems.append(f"costmodel watermark {dev!r}: high water "
                            f"below last sample")
    return problems


def validate_serve_block(obj) -> list[str]:
    """Schema check for the `"serve"` block (the sustained-load block
    `serve.loadgen.run_load` returns); returns problems (empty ==
    valid).  Pinned by `tests/test_serve.py`."""
    problems: list[str] = []
    if not isinstance(obj, dict):
        return [f"serve block is {type(obj).__name__}, not dict"]
    vps = obj.get("verifies_per_s")
    if not isinstance(vps, (int, float)) or isinstance(vps, bool) \
            or vps < 0:
        problems.append(f"'verifies_per_s' must be a non-negative "
                        f"number, got {vps!r}")
    for key in ("p50_ms", "p99_ms"):
        v = obj.get(key)
        if v is not None and (not isinstance(v, (int, float))
                              or isinstance(v, bool) or v < 0):
            problems.append(f"{key!r} must be a non-negative number or "
                            f"null, got {v!r}")
    p50, p99 = obj.get("p50_ms"), obj.get("p99_ms")
    if isinstance(p50, (int, float)) and isinstance(p99, (int, float)) \
            and p99 < p50:
        problems.append(f"p99_ms ({p99}) below p50_ms ({p50})")
    if not isinstance(obj.get("steady"), bool):
        problems.append("'steady' must be a bool")
    windows = obj.get("windows")
    if not isinstance(windows, list) or not all(
            isinstance(w, (int, float)) and not isinstance(w, bool)
            for w in windows):
        problems.append("'windows' must be a list of numbers")
    for key in ("submitted", "settled", "failed"):
        v = obj.get(key)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            problems.append(f"{key!r} must be a non-negative int, "
                            f"got {v!r}")
    qd = obj.get("queue_depth")
    if not isinstance(qd, dict) or not isinstance(qd.get("hist"), dict) \
            or not isinstance(qd.get("max"), int):
        problems.append("'queue_depth' must carry an int 'max' and a "
                        "'hist' dict")
    elif not all(isinstance(k, str) and isinstance(v, int)
                 for k, v in qd["hist"].items()):
        problems.append("queue_depth['hist'] must map str buckets to "
                        "int counts")
    if obj.get("mode") not in ("open", "closed"):
        problems.append(f"'mode' must be 'open' or 'closed', "
                        f"got {obj.get('mode')!r}")
    # request-tracing surface (PR 15): `latency_source` names the
    # percentile basis — "reqtrace" = per-request submit→complete
    # lifecycle records (queue wait + detours included), "executor" =
    # the legacy enqueue→batch-settle sample.  Optional for
    # backward-compat with pre-tracing blocks; a traced block must also
    # carry a schema-valid `latency_attribution` sub-object.
    src = obj.get("latency_source")
    if src is not None and src not in ("reqtrace", "executor"):
        problems.append(f"'latency_source' must be 'reqtrace' or "
                        f"'executor', got {src!r}")
    la = obj.get("latency_attribution")
    if src == "reqtrace" and la is None:
        problems.append("'latency_source' is 'reqtrace' but "
                        "'latency_attribution' is missing")
    if la is not None:
        problems.extend(validate_latency_attribution(la))
    # live-monitoring surface (SLO watchdog): optional — present on
    # rounds armed with CST_SLO_RULES
    slo = obj.get("slo")
    if slo is not None:
        problems.extend(validate_slo_block(slo))
    # device-occupancy surface: optional — present on rounds armed with
    # CST_OCCUPANCY
    occ = obj.get("occupancy")
    if occ is not None:
        problems.extend(validate_occupancy_block(occ))
    return problems


_SLO_PHASES = ("breach", "clear")


def validate_slo_block(obj) -> list[str]:
    """Schema check for the serve block's `"slo"` sub-object
    (`telemetry.monitor.Watchdog.slo_block`); returns problems (empty
    == valid).  Pinned by tests/test_monitor.py."""
    if not isinstance(obj, dict):
        return [f"slo block is {type(obj).__name__}, not dict"]
    problems: list[str] = []
    for key in ("ticks", "breaches", "events_dropped"):
        v = obj.get(key)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            problems.append(f"slo[{key!r}] must be a non-negative int, "
                            f"got {v!r}")
    if not isinstance(obj.get("clean"), bool):
        problems.append("slo['clean'] must be a bool")
    elif isinstance(obj.get("breaches"), int) \
            and obj["clean"] != (obj["breaches"] == 0):
        problems.append("slo['clean'] must equal (breaches == 0)")
    bn = obj.get("breaching_now")
    if not isinstance(bn, list) or not all(isinstance(n, str)
                                           for n in bn):
        problems.append("slo['breaching_now'] must be a list of rule "
                        "names")
    rules = obj.get("rules")
    if not isinstance(rules, list) or not rules:
        problems.append("slo['rules'] must be a non-empty list")
        rules = []
    for i, r in enumerate(rules):
        if not isinstance(r, dict) or not isinstance(r.get("name"), str):
            problems.append(f"slo rules[{i}] must be a dict with a "
                            f"str 'name'")
            continue
        for key in ("ticks", "breaches", "clears"):
            v = r.get(key)
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                problems.append(f"slo rules[{i}][{key!r}] must be a "
                                f"non-negative int, got {v!r}")
        if not isinstance(r.get("breaching"), bool):
            problems.append(f"slo rules[{i}]['breaching'] must be a "
                            f"bool")
        thr = r.get("threshold")
        if not isinstance(thr, (int, float)) or isinstance(thr, bool):
            problems.append(f"slo rules[{i}]['threshold'] must be a "
                            f"number, got {thr!r}")
    events = obj.get("events")
    if not isinstance(events, list):
        problems.append("slo['events'] must be a list")
        events = []
    for i, e in enumerate(events):
        if not isinstance(e, dict) or e.get("phase") not in _SLO_PHASES \
                or not isinstance(e.get("rule"), str) \
                or not isinstance(e.get("ts"), (int, float)):
            problems.append(f"slo events[{i}] must carry phase in "
                            f"{_SLO_PHASES}, a str 'rule' and a "
                            f"numeric 'ts'")
            break
    profiles = obj.get("profiles")
    if not isinstance(profiles, list) or not all(
            isinstance(p, str) for p in profiles):
        problems.append("slo['profiles'] must be a list of paths")
    return problems


_BUBBLE_CAUSES = ("host_prep", "queue_starved", "settle_serialized",
                  "drain")


def validate_occupancy_block(obj) -> list[str]:
    """Schema check for the serve block's `"occupancy"` sub-object
    (`telemetry.occupancy.block`); returns problems (empty == valid).
    Enforces the contiguity contract: busy plus the four bubble
    components must sum to the measured wall within 1e-6 relative.
    Pinned by tests/test_occupancy.py."""
    if not isinstance(obj, dict):
        return [f"occupancy block is {type(obj).__name__}, not dict"]
    problems: list[str] = []
    if not isinstance(obj.get("enabled"), bool):
        problems.append("occupancy['enabled'] must be a bool")
    for key in ("wall_s", "busy_s", "busy_frac"):
        v = obj.get(key)
        if not isinstance(v, (int, float)) or isinstance(v, bool) \
                or v < 0:
            problems.append(f"occupancy[{key!r}] must be a non-negative "
                            f"number, got {v!r}")
    for key in ("events", "events_dropped"):
        v = obj.get(key)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            problems.append(f"occupancy[{key!r}] must be a non-negative "
                            f"int, got {v!r}")
    bub = obj.get("bubbles_s")
    if not isinstance(bub, dict) or set(bub) != set(_BUBBLE_CAUSES):
        problems.append(f"occupancy['bubbles_s'] must map exactly the "
                        f"causes {_BUBBLE_CAUSES}")
        bub = None
    elif not all(isinstance(v, (int, float)) and not isinstance(v, bool)
                 and v >= -1e-9 for v in bub.values()):
        problems.append("occupancy bubble components must be "
                        "non-negative numbers")
        bub = None
    wall, busy = obj.get("wall_s"), obj.get("busy_s")
    if bub is not None and isinstance(wall, (int, float)) \
            and isinstance(busy, (int, float)) and wall > 0:
        total = busy + sum(bub.values())
        if abs(total - wall) > 1e-6 * max(wall, 1e-12):
            problems.append(f"occupancy busy+bubbles ({total}) != "
                            f"wall_s ({wall}) beyond 1e-6 relative")
    devs = obj.get("devices")
    if not isinstance(devs, dict):
        problems.append("occupancy['devices'] must be a dict")
        devs = {}
    for dev, blk in devs.items():
        if not isinstance(blk, dict) \
                or not isinstance(blk.get("busy_s"), (int, float)) \
                or not isinstance(blk.get("busy_frac"), (int, float)) \
                or not isinstance(blk.get("spans"), int) \
                or not isinstance(blk.get("bubbles_s"), dict):
            problems.append(f"occupancy device {dev!r} must carry "
                            f"busy_s, busy_frac, spans, bubbles_s")
    byk = obj.get("device_seconds_by_kind")
    if not isinstance(byk, dict) or not all(
            isinstance(k, str) and isinstance(v, (int, float))
            and not isinstance(v, bool) for k, v in byk.items()):
        problems.append("occupancy['device_seconds_by_kind'] must map "
                        "str kinds to numbers")
    ov = obj.get("overlap")
    if not isinstance(ov, dict) \
            or not isinstance(ov.get("prep_s"), (int, float)) \
            or not isinstance(ov.get("hidden_s"), (int, float)):
        problems.append("occupancy['overlap'] must carry numeric "
                        "prep_s and hidden_s")
    else:
        score = ov.get("score")
        if score is not None and (not isinstance(score, (int, float))
                                  or isinstance(score, bool)
                                  or not -1e-9 <= score <= 1 + 1e-9):
            problems.append(f"occupancy overlap score must be in "
                            f"[0, 1] or null, got {score!r}")
        if ov["prep_s"] > 0 and score is None:
            problems.append("occupancy overlap score must be present "
                            "when prep_s > 0")
    depth = obj.get("depth")
    if depth is not None and (not isinstance(depth, int)
                              or isinstance(depth, bool) or depth < 1):
        problems.append(f"occupancy['depth'] must be a positive int or "
                        f"null, got {depth!r}")
    return problems


_LATENCY_COMPONENTS = ("queue_wait", "batch_form", "device_wall",
                       "settle", "detour")
_LATENCY_OUTCOMES = ("ok", "recheck", "retry", "fallback", "shed",
                     "poisoned", "timeout")


def validate_latency_attribution(obj) -> list[str]:
    """Schema check for the serve block's `latency_attribution`
    sub-object (`telemetry.reqtrace.attribution`); returns problems
    (empty == valid).  Pinned by tests/test_reqtrace.py."""
    if not isinstance(obj, dict):
        return [f"latency_attribution is {type(obj).__name__}, not dict"]
    problems: list[str] = []
    kinds = obj.get("kinds")
    if not isinstance(kinds, dict):
        problems.append("latency_attribution['kinds'] must be a dict")
        kinds = {}
    for kind, blk in kinds.items():
        if not isinstance(blk, dict):
            problems.append(f"latency kind {kind!r} must be a dict")
            continue
        n = blk.get("count")
        if not isinstance(n, int) or isinstance(n, bool) or n < 1:
            problems.append(f"latency kind {kind!r}: 'count' must be a "
                            f"positive int, got {n!r}")
        for key in ("p50_ms", "p90_ms", "p99_ms"):
            v = blk.get(key)
            if not isinstance(v, (int, float)) or isinstance(v, bool) \
                    or v < 0:
                problems.append(f"latency kind {kind!r}: {key!r} must "
                                f"be a non-negative number, got {v!r}")
        p50, p99 = blk.get("p50_ms"), blk.get("p99_ms")
        if isinstance(p50, (int, float)) and isinstance(p99, (int, float)) \
                and p99 < p50:
            problems.append(f"latency kind {kind!r}: p99_ms ({p99}) "
                            f"below p50_ms ({p50})")
        for key in ("mean_components_ms", "p99_components_ms"):
            comp = blk.get(key)
            if not isinstance(comp, dict) or not all(
                    c in comp and isinstance(comp[c], (int, float))
                    and not isinstance(comp[c], bool) and comp[c] >= 0
                    for c in _LATENCY_COMPONENTS):
                problems.append(
                    f"latency kind {kind!r}: {key!r} must map every "
                    f"component {_LATENCY_COMPONENTS} to a non-negative "
                    f"number")
        oc = blk.get("outcomes")
        if not isinstance(oc, dict) or not all(
                k in _LATENCY_OUTCOMES and isinstance(v, int)
                for k, v in oc.items()):
            problems.append(f"latency kind {kind!r}: 'outcomes' must "
                            f"map outcomes in {_LATENCY_OUTCOMES} to "
                            f"int counts")
    frac = obj.get("p99_queue_frac")
    if frac is not None and (not isinstance(frac, (int, float))
                             or isinstance(frac, bool)
                             or not 0.0 <= frac <= 1.0):
        problems.append(f"'p99_queue_frac' must be in [0, 1] or null, "
                        f"got {frac!r}")
    worst = obj.get("worst")
    if not isinstance(worst, list):
        problems.append("'worst' must be a list of exemplar traces")
    else:
        for i, ex in enumerate(worst):
            if not isinstance(ex, dict) \
                    or not isinstance(ex.get("trace_id"), int) \
                    or not isinstance(ex.get("e2e_ms"), (int, float)) \
                    or not isinstance(ex.get("components_ms"), dict):
                problems.append(f"worst[{i}] must carry trace_id / "
                                f"e2e_ms / components_ms")
                break
    return problems


def validate_resilience_block(obj) -> list[str]:
    """Schema check for a chaos round's `"resilience"` sub-object
    (`resilience.chaos.run_chaos_load`); returns problems (empty ==
    valid).  Pinned by tests/test_resilience.py."""
    problems: list[str] = []
    if not isinstance(obj, dict):
        return [f"resilience block is {type(obj).__name__}, not dict"]
    if not isinstance(obj.get("chaos"), bool):
        problems.append("'chaos' must be a bool")
    for key in ("faults_injected", "wrong_results", "failed_requests",
                "checked_results", "retries", "fallbacks", "shed"):
        v = obj.get(key)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            problems.append(f"{key!r} must be a non-negative int, "
                            f"got {v!r}")
    for key in ("degraded_verifies_per_s", "recovery_latency_s",
                "baseline_verifies_per_s"):
        v = obj.get(key)
        if v is not None and (not isinstance(v, (int, float))
                              or isinstance(v, bool) or v < 0):
            problems.append(f"{key!r} must be a non-negative number or "
                            f"null, got {v!r}")
    if not isinstance(obj.get("recovered"), bool):
        problems.append("'recovered' must be a bool")
    if obj.get("recovered") and obj.get("recovery_latency_s") is None:
        problems.append("'recovered' is true but 'recovery_latency_s' "
                        "is null")
    br = obj.get("breaker")
    if not isinstance(br, dict) or not isinstance(
            br.get("transitions"), list) \
            or not isinstance(br.get("states"), dict):
        problems.append("'breaker' must carry a 'transitions' list and "
                        "a 'states' dict")
    else:
        for t in br["transitions"]:
            if not isinstance(t, dict) or not {"key", "from",
                                               "to"} <= set(t):
                problems.append(f"breaker transition {t!r} must carry "
                                f"key/from/to")
                break
    heal = obj.get("heal")
    if heal is not None:
        if not isinstance(heal, dict) \
                or not isinstance(heal.get("diverged"), bool):
            problems.append("'heal' must be a dict with a bool "
                            "'diverged'")
        elif heal["diverged"]:
            rs = heal.get("recovery_s")
            if not isinstance(rs, (int, float)) or isinstance(rs, bool) \
                    or rs < 0:
                problems.append("heal['recovery_s'] must be a "
                                "non-negative number when diverged")
    plan = obj.get("plan")
    if plan is not None and (not isinstance(plan, dict)
                             or not isinstance(plan.get("faults"), list)):
        problems.append("'plan' must be a fault-plan summary dict")
    fv = obj.get("fault_victims")
    if fv is not None:
        # blast-radius correlation (request tracing): which trace ids a
        # fault hit and how each settled.  `clean_ok` counts victims
        # that settled with a clean 'ok' — always zero by construction
        # (a fault-hit batch recovers as retry/fallback or poisons)
        if not isinstance(fv, dict) \
                or not isinstance(fv.get("count"), int) \
                or not isinstance(fv.get("trace_ids"), list) \
                or not isinstance(fv.get("outcomes"), dict):
            problems.append("'fault_victims' must carry int 'count', a "
                            "'trace_ids' list and an 'outcomes' dict")
        elif not all(isinstance(t, int) for t in fv["trace_ids"]):
            problems.append("fault_victims['trace_ids'] must be ints")
    problems.extend(validate_checkpoint_block(obj.get("checkpoint")))
    problems.extend(validate_mesh_block(obj.get("mesh")))
    fl = obj.get("flagship")
    if fl is not None:
        if not isinstance(fl, dict) \
                or not isinstance(fl.get("degraded_steps"), int) \
                or not isinstance(fl.get("wrong_results"), int):
            problems.append("'flagship' must carry int degraded_steps "
                            "and wrong_results")
    return problems


def validate_checkpoint_block(cp) -> list[str]:
    """Schema check for the chaos round's `"checkpoint"` sub-object
    (`resilience.chaos._checkpoint_segment`).  None is valid — the
    segment is part of chaos rounds only."""
    if cp is None:
        return []
    if not isinstance(cp, dict):
        return [f"checkpoint block is {type(cp).__name__}, not dict"]
    problems: list[str] = []
    for key in ("n_chunks", "journal_entries", "snapshot_bytes"):
        v = cp.get(key)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            problems.append(f"checkpoint[{key!r}] must be a "
                            f"non-negative int, got {v!r}")
    for key in ("restore_s", "rebuild_s", "journal_frac"):
        v = cp.get(key)
        if not isinstance(v, (int, float)) or isinstance(v, bool) \
                or v < 0:
            problems.append(f"checkpoint[{key!r}] must be a "
                            f"non-negative number, got {v!r}")
    if not isinstance(cp.get("parity"), bool):
        problems.append("checkpoint['parity'] must be a bool")
    sp = cp.get("speedup")
    if sp is not None and (not isinstance(sp, (int, float))
                           or isinstance(sp, bool) or sp < 0):
        problems.append(f"checkpoint['speedup'] must be a non-negative "
                        f"number or null, got {sp!r}")
    return problems


def validate_mesh_block(mesh) -> list[str]:
    """Schema check for the chaos round's `"mesh"` sub-object
    (`resilience.mesh.MeshVerifier.block` + the segment's correctness
    counters).  None and a `skipped` block (too few devices) are
    valid."""
    if mesh is None:
        return []
    if not isinstance(mesh, dict):
        return [f"mesh block is {type(mesh).__name__}, not dict"]
    if "skipped" in mesh:
        return []
    problems: list[str] = []
    for key in ("devices", "degraded_lanes", "max_degraded_lanes",
                "device_lost_events", "readmissions", "redispatches",
                "verified_statements", "lost_statements",
                "wrong_results", "checked_statements"):
        v = mesh.get(key)
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            problems.append(f"mesh[{key!r}] must be a non-negative "
                            f"int, got {v!r}")
    rl = mesh.get("recovery_latency_s")
    if rl is not None and (not isinstance(rl, (int, float))
                           or isinstance(rl, bool) or rl < 0):
        problems.append(f"mesh['recovery_latency_s'] must be a "
                        f"non-negative number or null, got {rl!r}")
    return problems
