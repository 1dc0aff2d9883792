"""Web dashboard — L7 (reference: `jepsen/src/jepsen/web.clj`).

A dependency-free HTTP dashboard over the store/ directory: a test
table colored by validity (web.clj:25-34,122), a file browser rooted at
the store (web.clj app :328), zip export of a whole test run
(web.clj:336 zip handler), plus the telemetry surfaces (ISSUE 4):
`/telemetry` lists runs with a telemetry.jsonl, `/telemetry/<name>/<ts>`
renders op-rate and p95-latency sparklines with nemesis fault windows
shaded and the `cli metrics` summary inline, and `/metrics` is the
process-global Prometheus text exposition for scraping.
`/elle/<name>/<ts>` renders the transactional anomaly section (ISSUE
5): per-checker isolation verdicts plus the elle.txt report inline.
`/live` + `/live/<name>/<ts>` render the live verification surfaces
(ISSUE 6): verdict-so-far, violation flags with detection lag, and the
cross-tenant micro-batch dispatch records, from the checker daemon's
live.json / live.jsonl.  Built on http.server so it runs anywhere the
framework does.
"""

from __future__ import annotations

import html
import io
import json
import threading
import zipfile
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from pathlib import Path
from urllib.parse import quote, unquote

from jepsen_tpu import store

VALID_COLORS = {True: "#ADF6B0", False: "#F3BBBC", None: "#EAEAEA"}
UNKNOWN_COLOR = "#F3EABB"


def _color(valid):
    if valid in VALID_COLORS:
        return VALID_COLORS[valid]
    return UNKNOWN_COLOR


def _page(title: str, body: str) -> bytes:
    return (f"<!DOCTYPE html><html><head><title>{html.escape(title)}"
            "</title><style>"
            "body{font-family:sans-serif;margin:2em}"
            "table{border-collapse:collapse}"
            "td,th{padding:.3em .8em;border:1px solid #ccc;text-align:left}"
            "a{text-decoration:none}"
            "</style></head><body>" + body + "</body></html>").encode()


# fast-tests memoization (web.clj:48-69): keyed on the results file's
# mtime as well as (name, ts), so a re-analysis of a stored history
# (which rewrites results.json in place) invalidates the cached verdict
# instead of pinning the stale one for the life of the process.
_results_cache: dict = {}
_results_cache_lock = threading.Lock()


def _cached_validity(name: str, ts: str):
    try:
        mtime = store.results_path(name, ts).stat().st_mtime_ns
    except OSError:
        return None                  # analysis still running: retry later
    key = (name, ts, mtime)
    with _results_cache_lock:
        if key in _results_cache:
            return _results_cache[key]
    res = store.load_results(name, ts)
    if res is None:
        return None
    valid = res.get("valid?")
    with _results_cache_lock:
        # Drop stale entries for this run so the cache stays bounded by
        # the number of distinct runs, not rewrites.  The server is
        # threaded; iteration and mutation stay under the lock.
        for k in [k for k in _results_cache if k[:2] == (name, ts)]:
            del _results_cache[k]
        _results_cache[key] = valid
    return valid


def _test_rows() -> list:
    rows = []
    for name, stamps in sorted(store.tests().items()):
        for ts in sorted(stamps, reverse=True):
            rows.append((name, ts, _cached_validity(name, ts)))
    rows.sort(key=lambda r: r[1], reverse=True)
    return rows


def home_html() -> bytes:
    rows = []
    for name, ts, valid in _test_rows():
        base = f"/files/{quote(name)}/{quote(ts)}"
        # anomaly-section link only for runs an elle checker rendered
        # (a cheap existence probe, like the telemetry index)
        elle = ""
        if (store.BASE / store._sanitize(name) / ts
                / "elle.txt").exists():
            elle = (f"<a href='/elle/{quote(name)}/{quote(ts)}'>"
                    "anomalies</a>")
        rows.append(
            f"<tr style='background:{_color(valid)}'>"
            f"<td>{html.escape(name)}</td>"
            f"<td><a href='{base}/'>{html.escape(ts)}</a></td>"
            f"<td>{html.escape(json.dumps(valid))}</td>"
            f"<td><a href='{base}/results.json'>results</a></td>"
            f"<td><a href='{base}/history.txt'>history</a></td>"
            f"<td>{elle}</td>"
            f"<td><a href='/zip/{quote(name)}/{quote(ts)}'>zip</a></td>"
            "</tr>")
    body = ("<h1>Jepsen</h1><p><a href='/telemetry'>telemetry</a> &middot; "
            "<a href='/live'>live</a> &middot; "
            "<a href='/fleet'>fleet</a> &middot; "
            "<a href='/ingest'>ingest</a> &middot; "
            "<a href='/trace'>traces</a> &middot; "
            "<a href='/campaign'>campaigns</a> &middot; "
            "<a href='/metrics'>metrics</a></p>"
            "<table><tr><th>Test</th><th>Time</th>"
            "<th>Valid?</th><th>Results</th><th>History</th>"
            "<th>Anomalies</th><th>Zip</th>"
            "</tr>" + "".join(rows) + "</table>")
    return _page("Jepsen", body)


def _safe_path(rel: str) -> Path:
    """Resolve an already-decoded path under the store root, refusing
    traversal (containment via relative_to, not string prefix — a
    sibling like store-backup/ must not pass)."""
    base = store.BASE.resolve()
    p = (base / rel.lstrip("/")).resolve()
    try:
        p.relative_to(base)
    except ValueError:
        raise PermissionError(rel)
    return p


def dir_html(rel: str, p: Path) -> bytes:
    """rel is the decoded store-relative path; links re-encode it."""
    ents = []
    rel = rel.strip("/")
    for child in sorted(p.iterdir()):
        slash = "/" if child.is_dir() else ""
        href = "/files/" + quote(f"{rel}/{child.name}" if rel
                                 else child.name) + slash
        ents.append(f"<li><a href='{href}'>"
                    f"{html.escape(child.name)}{slash}</a></li>")
    return _page(rel or "store",
                 f"<h1>{html.escape(rel or 'store')}</h1><p>"
                 "<a href='/'>&larr; tests</a></p><ul>"
                 + "".join(ents) + "</ul>")


# ---------------------------------------------------------------------------
# Telemetry pages (ISSUE 4): /telemetry index, per-run sparklines with
# nemesis windows shaded, /metrics Prometheus exposition
# ---------------------------------------------------------------------------

def _sparkline_svg(values: list, windows: list, color: str,
                   width: int = 640, height: int = 80,
                   label: str = "") -> str:
    """Inline SVG polyline over bucketed values; fault windows shaded
    as translucent rectangles spanning the full height."""
    if not values:
        return "<p>(no data)</p>"
    vmax = max(values) or 1.0
    n = len(values)
    pts = " ".join(
        f"{i / max(n - 1, 1) * width:.1f},"
        f"{height - (v / vmax) * (height - 4):.1f}"
        for i, v in enumerate(values))
    shades = "".join(
        f"<rect x='{a * width:.1f}' y='0' "
        f"width='{max((b - a) * width, 1.0):.1f}' height='{height}' "
        "fill='#E8A4A4' fill-opacity='0.35'/>"
        for a, b in windows)
    return (f"<div><b>{html.escape(label)}</b> "
            f"(max {vmax:.3g})<br>"
            f"<svg width='{width}' height='{height}' "
            "style='border:1px solid #ccc;background:#fff'>"
            + shades +
            f"<polyline points='{pts}' fill='none' stroke='{color}' "
            "stroke-width='1.5'/></svg></div>")


def telemetry_index_html() -> bytes:
    rows = []
    for name, ts, valid in _test_rows():
        if not (store.BASE / store._sanitize(name) / ts
                / "telemetry.jsonl").exists():
            continue
        rows.append(
            f"<tr style='background:{_color(valid)}'>"
            f"<td>{html.escape(name)}</td>"
            f"<td><a href='/telemetry/{quote(name)}/{quote(ts)}'>"
            f"{html.escape(ts)}</a></td>"
            f"<td><a href='/files/{quote(name)}/{quote(ts)}/"
            "telemetry.jsonl'>raw</a></td></tr>")
    body = ("<h1>Telemetry</h1><p><a href='/'>&larr; tests</a> &middot; "
            "<a href='/metrics'>prometheus snapshot</a></p>"
            "<table><tr><th>Test</th><th>Run</th><th>Log</th></tr>"
            + "".join(rows) + "</table>")
    if not rows:
        body += "<p>(no runs with a telemetry.jsonl yet)</p>"
    return _page("Telemetry", body)


def _find_elle_results(tree, path="results") -> list:
    """Recursively collect elle verdicts (dicts carrying
    anomaly-types + txn-count) out of a results tree."""
    out = []
    if isinstance(tree, dict):
        if "anomaly-types" in tree and "txn-count" in tree:
            out.append((path, tree))
        else:
            for k, v in tree.items():
                out.extend(_find_elle_results(v, f"{path}/{k}"))
    return out


def elle_html(name: str, ts: str) -> bytes:
    """Transactional anomaly section for one run: per-checker verdict
    rows (weakest violated isolation level, anomaly types, engine)
    plus the rendered elle.txt report inline."""
    body = [f"<h1>{html.escape(name)} / {html.escape(ts)} "
            "&mdash; transactional isolation</h1>",
            "<p><a href='/'>&larr; tests</a></p>"]
    res = store.load_results(name, ts)
    rows = _find_elle_results(res) if res else []
    if rows:
        cells = []
        for path, r in rows:
            kinds = r.get("anomaly-types") or []
            color = _color(r.get("valid?"))
            cells.append(
                f"<tr style='background:{color}'>"
                f"<td>{html.escape(path)}</td>"
                f"<td>{html.escape(json.dumps(r.get('valid?')))}</td>"
                f"<td>{r.get('txn-count')}</td>"
                f"<td>{html.escape(', '.join(kinds) or '-')}</td>"
                f"<td>{html.escape(r.get('weakest-violated') or '-')}"
                "</td>"
                f"<td>{html.escape(r.get('engine') or '?')}</td></tr>")
        body.append("<table><tr><th>Checker</th><th>Valid?</th>"
                    "<th>Txns</th><th>Anomalies</th>"
                    "<th>Weakest violated</th><th>Engine</th></tr>"
                    + "".join(cells) + "</table>")
    else:
        body.append("<p>(no transactional isolation verdicts in "
                    "results.json)</p>")
    try:
        p = _safe_path(f"{name}/{ts}") / "elle.txt"
        if p.exists():
            body.append("<h2>Anomaly report</h2><pre>"
                        + html.escape(p.read_text()) + "</pre>")
    except (OSError, PermissionError):
        pass
    return _page(f"elle {name}/{ts}", "".join(body))


# ---------------------------------------------------------------------------
# Live verification pages (ISSUE 6): /live index + per-run
# verdict-so-far, detection flags, and micro-batch dispatch records —
# rendered from the checker daemon's live.json / live.jsonl surfaces
# ---------------------------------------------------------------------------

_LIVE_COLORS = {True: "#ADF6B0", False: "#F3BBBC",
                "unknown": "#F3EABB"}


def _live_color(verdict):
    return _LIVE_COLORS.get(verdict, "#EAEAEA")


def live_index_html() -> bytes:
    rows = []
    for name, stamps in sorted(store.tests().items()):
        for ts in sorted(stamps, reverse=True):
            p = store.BASE / store._sanitize(name) / ts / "live.json"
            if not p.exists():
                continue
            try:
                with open(p) as f:
                    lj = json.load(f)
            except (OSError, json.JSONDecodeError):
                continue
            v = lj.get("verdict-so-far")
            txn = lj.get("txn") or {}
            weakest = txn.get("weakest-violated")
            rows.append(
                f"<tr style='background:{_live_color(v)}'>"
                f"<td>{html.escape(name)}</td>"
                f"<td><a href='/live/{quote(name)}/{quote(ts)}'>"
                f"{html.escape(ts)}</a></td>"
                f"<td>{html.escape(json.dumps(v))}</td>"
                f"<td>{html.escape(weakest) if weakest else '&mdash;'}"
                "</td>"
                f"<td>{lj.get('ops_checked', 0)}</td>"
                f"<td>{lj.get('windows_checked', 0)}</td>"
                f"<td>{len(lj.get('flags') or [])}</td>"
                f"<td>{'yes' if lj.get('done') else 'tailing'}</td>"
                "</tr>")
    body = ("<h1>Live verification</h1>"
            "<p><a href='/'>&larr; tests</a> &middot; "
            "<a href='/metrics'>metrics</a></p>"
            "<table><tr><th>Test</th><th>Run</th>"
            "<th>Verdict so far</th><th>Weakest violated</th>"
            "<th>Ops checked</th>"
            "<th>Windows</th><th>Flags</th><th>Done?</th></tr>"
            + "".join(rows) + "</table>")
    if not rows:
        body += ("<p>(no runs under live checking — start "
                 "<code>python -m jepsen_tpu.cli serve-checker "
                 "store/</code>)</p>")
    return _page("Live verification", body)


def live_run_html(name: str, ts: str) -> bytes:
    from jepsen_tpu import telemetry
    d = _safe_path(f"{name}/{ts}")
    lj_path = d / "live.json"
    if not lj_path.exists():
        raise FileNotFoundError(lj_path)
    with open(lj_path) as f:
        lj = json.load(f)
    v = lj.get("verdict-so-far")
    body = [f"<h1>{html.escape(name)} / {html.escape(ts)} "
            "&mdash; live verification</h1>",
            "<p><a href='/live'>&larr; live</a> &middot; "
            f"<a href='/files/{quote(name)}/{quote(ts)}/live.jsonl'>"
            "raw event log</a></p>",
            f"<p style='background:{_live_color(v)};padding:.5em'>"
            f"<b>verdict so far: {html.escape(json.dumps(v))}</b> "
            f"({'run complete' if lj.get('done') else 'still tailing'}"
            ")</p>"]
    txn = lj.get("txn") or {}
    if txn:
        weakest = txn.get("weakest-violated")
        body.append(
            "<h2>Transactional (incremental Elle)</h2>"
            f"<p><b>weakest violated level so far: "
            f"{html.escape(weakest) if weakest else 'none (clean)'}"
            "</b></p><table>"
            + "".join(
                f"<tr><th>{html.escape(k)}</th>"
                f"<td>{html.escape(json.dumps(txn.get(k), default=repr))}"
                "</td></tr>"
                for k in ("workload", "txns", "keys", "anomalies",
                          "lattice_classes", "lattice_seconds",
                          "windows", "closure_rebuilds",
                          "resumed_txns", "engine", "rounds",
                          "n_pad", "flags_capped"))
            + "</table>")
    body.append(
        "<table>"
        + "".join(f"<tr><th>{html.escape(k)}</th>"
                  f"<td>{html.escape(json.dumps(lj.get(k), default=repr))}"
                  "</td></tr>"
                  for k in ("ops_ingested", "ops_checked",
                            "windows_checked", "lanes", "queue_depth",
                            "bytes", "evictions", "backend",
                            "plan_cache", "paused", "corrupt",
                            "saturated"))
        + "</table>")
    events = []
    ev_path = d / "live.jsonl"
    if ev_path.exists():
        events = telemetry.read_events(ev_path)
    flags = [e for e in events if e.get("type") == "live-flag"]
    if flags:
        body.append("<h2>Violation flags</h2>"
                    "<table><tr><th>Lane</th><th>Op index</th>"
                    "<th>f</th><th>Value</th>"
                    "<th>Detection lag (s)</th><th>Dispatch</th>"
                    "<th>Engine</th><th>Plan cache</th></tr>")
        for e in flags:
            body.append(
                "<tr style='background:#F3BBBC'>"
                f"<td>{html.escape(str(e.get('lane')))}</td>"
                f"<td>{e.get('op_index')}</td>"
                f"<td>{html.escape(str(e.get('f')))}</td>"
                f"<td>{html.escape(str(e.get('value')))}</td>"
                f"<td>{e.get('detection_lag_s')}</td>"
                f"<td>{html.escape(str(e.get('dispatch_id')))}</td>"
                f"<td>{html.escape(str(e.get('engine')))}</td>"
                f"<td>{html.escape(str(e.get('cache')))}</td></tr>")
        body.append("</table>")
    disps = [e for e in events if e.get("type") == "live-dispatch"]
    if disps:
        body.append("<h2>Micro-batch dispatches</h2>"
                    "<table><tr><th>Id</th><th>Engine</th>"
                    "<th>Plan cache</th><th>Lanes</th>"
                    "<th>Tenants</th><th>Bucket (T,E,M,Sn)</th>"
                    "<th>Seconds</th></tr>")
        for e in disps[-50:]:
            shared = len(e.get("tenants") or []) > 1
            body.append(
                f"<tr{' style=background:#D8E8F8' if shared else ''}>"
                f"<td>{html.escape(str(e.get('dispatch_id')))}</td>"
                f"<td>{html.escape(str(e.get('engine')))}</td>"
                f"<td>{html.escape(str(e.get('cache')))}</td>"
                f"<td>{e.get('lanes')}</td>"
                f"<td>{html.escape(', '.join(e.get('tenants') or []))}"
                "</td>"
                f"<td>{html.escape(str(e.get('bucket')))}</td>"
                f"<td>{e.get('seconds')}</td></tr>")
        body.append("</table>")
    windows = [e for e in events if e.get("type") == "live-window"]
    lags = sorted(e["lag_s"] for e in windows
                  if isinstance(e.get("lag_s"), (int, float)))
    if lags:
        p99 = lags[min(int(0.99 * len(lags)), len(lags) - 1)]
        body.append(f"<p>{len(windows)} windows checked; "
                    f"op-append&rarr;verdict lag p50="
                    f"{lags[len(lags) // 2]:.4f}s "
                    f"p99={p99:.4f}s max={lags[-1]:.4f}s</p>")
    return _page(f"live {name}/{ts}", "".join(body))


# ---------------------------------------------------------------------------
# Fleet page (ISSUE 14): /fleet — the serve-checker fleet aggregate:
# workers (from store/fleet/<id>.json status sidecars), lease-owned
# tenants with owner/epoch/cursor, the takeover/fencing timeline
# (lease-* events merged from tenant live.jsonl + worker fleet logs),
# and runs nobody ever owned, visibly flagged rather than absent
# ---------------------------------------------------------------------------

def _fleet_workers() -> list:
    out = []
    root = store.fleet_root()
    if not root.is_dir():
        return out
    for p in sorted(root.glob("*.json")):
        try:
            with open(p) as f:
                out.append(json.load(f))
        except (OSError, json.JSONDecodeError):
            continue
    return out


def _fleet_tenants() -> list:
    """(name, ts, lease-dict-or-None, live-dict-or-None) for every run
    dir carrying a history.wal."""
    from jepsen_tpu.live import lease as lease_mod
    rows = []
    for name, stamps in sorted(store.tests().items()):
        for ts in sorted(stamps, reverse=True):
            d = store.BASE / store._sanitize(name) / ts
            if not (d / "history.wal").exists():
                continue
            ls = lease_mod.read(d)
            lj = None
            try:
                with open(d / "live.json") as f:
                    lj = json.load(f)
            except (OSError, json.JSONDecodeError):
                pass
            rows.append((name, ts, ls, lj))
    return rows


def _fleet_events(limit: int = 50) -> list:
    """The takeover timeline: lease-* events from every tenant's
    live.jsonl merged with the workers' own fleet logs (the home of
    lease-fenced refusals), newest first."""
    from jepsen_tpu import telemetry
    evs = []
    for name, ts, ls, _lj in _fleet_tenants():
        if ls is None:
            continue
        p = store.BASE / store._sanitize(name) / ts / "live.jsonl"
        if not p.exists():
            continue
        for e in telemetry.read_events(p):
            if str(e.get("type", "")).startswith("lease-"):
                evs.append(dict(e, tenant=f"{name}/{ts}"))
    root = store.fleet_root()
    if root.is_dir():
        for p in sorted(root.glob("*.jsonl")):
            for e in telemetry.read_events(p):
                if str(e.get("type", "")).startswith("lease-"):
                    evs.append(e)
    evs.sort(key=lambda e: e.get("t") or 0.0, reverse=True)
    return evs[:limit]


def fleet_html() -> bytes:
    import time as time_mod
    now = time_mod.time()
    body = ["<h1>Checker fleet</h1>",
            "<p><a href='/'>&larr; tests</a> &middot; "
            "<a href='/live'>live</a> &middot; "
            "<a href='/metrics'>metrics</a></p>"]

    workers = _fleet_workers()
    if workers:
        body.append("<h2>Workers</h2>"
                    "<table><tr><th>Worker</th><th>Owned</th>"
                    "<th>Flags</th><th>Takeovers</th>"
                    "<th>Fenced writes</th>"
                    "<th>Max takeover lag (s)</th>"
                    "<th>Window lag p50/p99 (s)</th>"
                    "<th>Last beat</th></tr>")
        for w in workers:
            age = now - w.get("updated", 0)
            ttl = w.get("lease_ttl") or 5.0
            stale = age > 3 * ttl
            body.append(
                f"<tr{' style=background:#F3EABB' if stale else ''}>"
                f"<td>{html.escape(str(w.get('worker')))}</td>"
                f"<td>{w.get('owned')}</td>"
                f"<td>{w.get('flags_total')}</td>"
                f"<td>{w.get('takeovers')}</td>"
                f"<td>{w.get('fenced_writes')}</td>"
                f"<td>{w.get('max_takeover_lag_s')}</td>"
                f"<td>{w.get('lag_p50_s')} / {w.get('lag_p99_s')}</td>"
                f"<td>{age:.1f}s ago"
                f"{' (stale)' if stale else ''}</td></tr>")
        body.append("</table>")
    else:
        body.append("<p>(no worker status files under store/fleet/ — "
                    "start workers with <code>serve-checker store/ "
                    "--workers 2</code> or <code>--lease-ttl "
                    "5</code>)</p>")

    tenants = _fleet_tenants()
    owned_rows, never_rows = [], []
    for name, ts, ls, lj in tenants:
        v = (lj or {}).get("verdict-so-far")
        if ls is None:
            never_rows.append(
                "<tr style='background:#F3EABB'>"
                f"<td>{html.escape(name)}/"
                f"<a href='/live/{quote(name)}/{quote(ts)}'>"
                f"{html.escape(ts)}</a></td>"
                "<td colspan=4><b>never owned</b>"
                + (" &mdash; " + html.escape(str(
                    (lj or {}).get("reason")))
                   if (lj or {}).get("unowned") else "")
                + "</td>"
                f"<td>{html.escape(json.dumps(v))}</td></tr>")
            continue
        status = "done" if ls.done else \
            ("released" if ls.released else
             ("torn" if ls.corrupt else "held"))
        owned_rows.append(
            f"<tr style='background:{_live_color(v)}'>"
            f"<td>{html.escape(name)}/"
            f"<a href='/live/{quote(name)}/{quote(ts)}'>"
            f"{html.escape(ts)}</a></td>"
            f"<td>{html.escape(str(ls.owner))}</td>"
            f"<td>{ls.epoch}</td>"
            f"<td>{html.escape(status)}</td>"
            f"<td>{ls.offset}/{ls.seq}</td>"
            f"<td>{html.escape(json.dumps(v))}</td></tr>")
    if owned_rows or never_rows:
        body.append("<h2>Tenants</h2>"
                    "<table><tr><th>Run</th><th>Owner</th>"
                    "<th>Epoch</th><th>Lease</th>"
                    "<th>Safe cursor (off/seq)</th>"
                    "<th>Verdict so far</th></tr>"
                    + "".join(owned_rows) + "".join(never_rows)
                    + "</table>")

    evs = _fleet_events()
    if evs:
        body.append("<h2>Takeover / fencing timeline</h2>"
                    "<table><tr><th>When</th><th>Event</th>"
                    "<th>Tenant</th><th>Worker</th><th>Epoch</th>"
                    "<th>Detail</th></tr>")
        for e in evs:
            t = e.get("t")
            detail = []
            if e.get("from_worker"):
                detail.append(f"from {e['from_worker']}")
            if e.get("silent_s") is not None:
                detail.append(f"silent {e['silent_s']}s")
            if e.get("reason"):
                detail.append(str(e["reason"]))
            if e.get("cursor"):
                detail.append(f"cursor {e['cursor']}")
            color = {"lease-takeover": "#D8E8F8",
                     "lease-fenced": "#F3BBBC"}.get(e.get("type"), "")
            body.append(
                f"<tr{f' style=background:{color}' if color else ''}>"
                f"<td>{now - t:.1f}s ago</td>" if t else
                "<tr><td>?</td>")
            body.append(
                f"<td>{html.escape(str(e.get('type')))}</td>"
                f"<td>{html.escape(str(e.get('tenant', '-')))}</td>"
                f"<td>{html.escape(str(e.get('worker', '-')))}</td>"
                f"<td>{e.get('epoch', '')}</td>"
                f"<td>{html.escape('; '.join(detail))}</td></tr>")
        body.append("</table>")
    return _page("Checker fleet", "".join(body))


# ---------------------------------------------------------------------------
# Ingest page (ISSUE 16): /ingest — the remote-tenant network tier:
# listeners (from store/ingest/<server>.json status sidecars),
# connected tenants with writer/epoch/cursor/backlog/backpressure
# state, and the fenced-rejection + frame-fault timeline from the
# servers' journals (store/ingest/<server>.jsonl)
# ---------------------------------------------------------------------------

def _ingest_servers() -> list:
    out = []
    root = store.ingest_root()
    if not root.is_dir():
        return out
    for p in sorted(root.glob("*.json")):
        try:
            with open(p) as f:
                out.append(json.load(f))
        except (OSError, json.JSONDecodeError):
            continue
    return out


def _ingest_events(limit: int = 50) -> list:
    """The network-fault timeline: fenced registrations and torn/dup/
    reordered frames from every server's journal, newest first."""
    from jepsen_tpu import telemetry
    evs = []
    root = store.ingest_root()
    if root.is_dir():
        for p in sorted(root.glob("*.jsonl")):
            for e in telemetry.read_events(p):
                if str(e.get("type", "")).startswith("ingest-"):
                    evs.append(e)
    evs.sort(key=lambda e: e.get("t") or 0.0, reverse=True)
    return evs[:limit]


def ingest_html() -> bytes:
    import time as time_mod
    now = time_mod.time()
    body = ["<h1>Remote ingest</h1>",
            "<p><a href='/'>&larr; tests</a> &middot; "
            "<a href='/fleet'>fleet</a> &middot; "
            "<a href='/live'>live</a> &middot; "
            "<a href='/metrics'>metrics</a></p>"]

    servers = _ingest_servers()
    if servers:
        body.append("<h2>Listeners</h2>"
                    "<table><tr><th>Server</th><th>Listen</th>"
                    "<th>Tenants</th><th>Frames ok</th>"
                    "<th>Torn</th><th>Dup</th><th>Reorder</th>"
                    "<th>Fenced</th><th>Resumes</th>"
                    "<th>Last beat</th></tr>")
        for s in servers:
            age = now - (s.get("updated") or 0)
            stale = age > 10.0
            c = s.get("counts") or {}
            body.append(
                f"<tr{' style=background:#F3EABB' if stale else ''}>"
                f"<td>{html.escape(str(s.get('server')))}</td>"
                f"<td>{html.escape(str(s.get('host')))}:"
                f"{s.get('port')}</td>"
                f"<td>{len(s.get('tenants') or {})}"
                f"/{s.get('known_tenants')}</td>"
                f"<td>{c.get('ok')}</td><td>{c.get('torn')}</td>"
                f"<td>{c.get('dup')}</td><td>{c.get('reorder')}</td>"
                f"<td>{c.get('fenced')}</td>"
                f"<td>{c.get('resumes')}</td>"
                f"<td>{age:.1f}s ago"
                f"{' (stale)' if stale else ''}</td></tr>")
        body.append("</table>")
    else:
        body.append("<p>(no listener status files under store/ingest/ "
                    "— start one with <code>serve-checker store/ "
                    "--listen 127.0.0.1:7419</code>)</p>")

    tenant_rows = []
    for s in servers:
        for tenant, t in sorted((s.get("tenants") or {}).items()):
            f = t.get("frames") or {}
            paused = t.get("paused")
            tenant_rows.append(
                f"<tr{' style=background:#F3EABB' if paused else ''}>"
                f"<td>{html.escape(tenant)}</td>"
                f"<td>{html.escape(str(s.get('server')))}</td>"
                f"<td>{html.escape(str(t.get('writer')))}</td>"
                f"<td>{t.get('epoch')}</td>"
                f"<td>{t.get('offset')}/{t.get('seq')}</td>"
                f"<td>{t.get('backlog')}</td>"
                f"<td>{'<b>paused</b>' if paused else 'flowing'}</td>"
                f"<td>{f.get('torn', 0)}/{f.get('dup', 0)}"
                f"/{f.get('reorder', 0)}</td></tr>")
    if tenant_rows:
        body.append("<h2>Connected tenants</h2>"
                    "<table><tr><th>Tenant</th><th>Server</th>"
                    "<th>Writer</th><th>Epoch</th>"
                    "<th>Cursor (off/seq)</th>"
                    "<th>Backlog (bytes)</th><th>Flow</th>"
                    "<th>Torn/dup/reorder</th></tr>"
                    + "".join(tenant_rows) + "</table>")

    evs = _ingest_events()
    if evs:
        body.append("<h2>Fencing / frame-fault timeline</h2>"
                    "<table><tr><th>When</th><th>Event</th>"
                    "<th>Tenant</th><th>Server</th><th>Seq</th>"
                    "<th>Detail</th></tr>")
        for e in evs:
            t = e.get("t")
            detail = []
            if e.get("why"):
                detail.append(str(e["why"]))
            if e.get("writer"):
                detail.append(f"writer {e['writer']}")
            if e.get("epoch") is not None:
                detail.append(f"epoch {e['epoch']}")
            if e.get("resumed"):
                detail.append("resumed")
            color = {"ingest-fenced": "#F3BBBC",
                     "ingest-torn": "#F3EABB",
                     "ingest-dup": "#F3EABB",
                     "ingest-reorder": "#F3EABB",
                     "ingest-pause": "#D8E8F8"}.get(e.get("type"), "")
            body.append(
                f"<tr{f' style=background:{color}' if color else ''}>"
                f"<td>{now - t:.1f}s ago</td>" if t else
                "<tr><td>?</td>")
            body.append(
                f"<td>{html.escape(str(e.get('type')))}</td>"
                f"<td>{html.escape(str(e.get('tenant', '-')))}</td>"
                f"<td>{html.escape(str(e.get('server', '-')))}</td>"
                f"<td>{e.get('seq', '')}</td>"
                f"<td>{html.escape('; '.join(detail))}</td></tr>")
        body.append("</table>")
    return _page("Remote ingest", "".join(body))


# ---------------------------------------------------------------------------
# Campaign pages (ISSUE 13): /campaign index + per-campaign coverage
# matrix (nemesis x workload x anomaly class, gaps visible) — rendered
# from store/campaigns/<name>/{status,coverage}.json
# ---------------------------------------------------------------------------

def _campaign_safe_dir(name: str) -> Path:
    base = store.campaigns_root().resolve()
    p = (base / name).resolve()
    try:
        p.relative_to(base)
    except ValueError:
        raise PermissionError(name)
    return p


def campaign_index_html() -> bytes:
    rows = []
    root = store.campaigns_root()
    names = sorted(p.name for p in root.iterdir()
                   if p.is_dir()) if root.is_dir() else []
    for n in names:
        st = {}
        sp = root / n / "status.json"
        if sp.exists():
            try:
                with open(sp) as f:
                    st = json.load(f)
            except (OSError, json.JSONDecodeError):
                pass
        state = (f"done ({st.get('reason')})" if st.get("done")
                 else "in progress")
        rows.append(
            "<tr>"
            f"<td><a href='/campaign/{quote(n)}'>{html.escape(n)}</a>"
            "</td>"
            f"<td>{html.escape(str(st.get('sut', '?')))}</td>"
            f"<td>{st.get('seed', '?')}</td>"
            f"<td>{st.get('run', 0)}/{st.get('budget', '?')}</td>"
            f"<td>{st.get('novel', 0)}</td>"
            f"<td>{st.get('deduped', 0)}</td>"
            f"<td>{st.get('quarantined', 0)}</td>"
            f"<td>{st.get('leaks', 0)}</td>"
            f"<td>{html.escape(state)}</td></tr>")
    body = ("<h1>Nemesis campaigns</h1>"
            "<p><a href='/'>&larr; tests</a></p>"
            "<table><tr><th>Campaign</th><th>SUT</th><th>Seed</th>"
            "<th>Schedules</th><th>Novel</th><th>Deduped</th>"
            "<th>Quarantined</th><th>Leaks</th><th>State</th></tr>"
            + "".join(rows) + "</table>")
    if not rows:
        body += ("<p>(no campaigns — start one with "
                 "<code>python -m jepsen_tpu.cli campaign run</code>)"
                 "</p>")
    return _page("Campaigns", body)


def campaign_html(name: str) -> bytes:
    """The coverage matrix: one table per workload, nemesis rows x
    anomaly-class columns — EVERY registry nemesis gets a row, so a
    fault class the search never produced coverage for is a visible
    gap, not a missing line."""
    d = _campaign_safe_dir(name)
    if not d.is_dir():
        raise FileNotFoundError(name)
    st, cov = {}, {}
    for fname, box in (("status.json", st), ("coverage.json", cov)):
        p = d / fname
        if p.exists():
            try:
                with open(p) as f:
                    box.update(json.load(f))
            except (OSError, json.JSONDecodeError):
                pass
    body = [f"<h1>campaign {html.escape(name)}</h1>",
            "<p><a href='/campaign'>&larr; campaigns</a> &middot; "
            f"<a href='/files/campaigns/{quote(name)}/ledger.jsonl'>"
            "raw ledger</a></p>"]
    if st:
        body.append(
            "<p>" + " &middot; ".join(
                f"<b>{html.escape(k)}</b>: "
                f"{html.escape(json.dumps(st.get(k)))}"
                for k in ("sut", "seed", "run", "budget", "novel",
                          "deduped", "quarantined", "crashed",
                          "leaks", "signatures", "frontier", "dry",
                          "done", "reason")) + "</p>")
    nemeses = cov.get("nemeses") or []
    workloads = cov.get("workloads") or []
    cells = cov.get("cells") or {}
    classes = sorted({cls for wl in cells.values()
                      for cc in wl.values() for cls in cc})
    if not classes:
        classes = ["none"]
    for wl in workloads:
        body.append(f"<h2>workload: {html.escape(wl)}</h2>"
                    "<table><tr><th>Nemesis</th>"
                    + "".join(f"<th>{html.escape(c)}</th>"
                              for c in classes) + "</tr>")
        for n in nemeses:
            row = (cells.get(n) or {}).get(wl) or {}
            tds = []
            for c in classes:
                v = row.get(c, 0)
                # gaps (never-covered cells) stay visibly grey
                style = "" if v else " style='background:#EAEAEA'"
                tds.append(f"<td{style}>{v or ''}</td>")
            covered = bool(row)
            nm_style = "" if covered else \
                " style='background:#F3EABB'"
            body.append(f"<tr><td{nm_style}>{html.escape(n)}</td>"
                        + "".join(tds) + "</tr>")
        body.append("</table>")
    if not workloads:
        body.append("<p>(no coverage yet)</p>")
    return _page(f"campaign {name}", "".join(body))


def telemetry_run_html(name: str, ts: str) -> bytes:
    from jepsen_tpu import telemetry
    p = _safe_path(f"{name}/{ts}") / "telemetry.jsonl"
    if not p.exists():
        raise FileNotFoundError(p)
    events = telemetry.read_events(p)
    series = telemetry.op_series(events)
    body = [f"<h1>{html.escape(name)} / {html.escape(ts)}</h1>",
            "<p><a href='/telemetry'>&larr; telemetry</a></p>"]
    if series["rate"]:
        span = series["t1"] - series["t0"]
        body.append(f"<p>{span:.1f}s of ops; shaded bands are nemesis "
                    "fault windows</p>")
        body.append(_sparkline_svg(series["rate"], series["windows"],
                                   "#3B6EA5", label="op rate (ops/s)"))
        body.append(_sparkline_svg(series["p95_ms"], series["windows"],
                                   "#A5703B",
                                   label="op latency p95 (ms)"))
    body.append(_dispatch_plans_html(events))
    body.append("<h2>Summary</h2><pre>"
                + html.escape(telemetry.summarize(events)) + "</pre>")
    return _page(f"telemetry {name}/{ts}", "".join(body))


def _dispatch_plans_html(events) -> str:
    """The dispatch-plans panel (ISSUE 8): one row per distinct
    planner-emitted plan — engine, WHY it was chosen, the fallback
    chain below it, the compiled-shape bucket, the pack backend and
    the deep shard — rendered from the `plan` field attach_dispatch
    records on every verdict."""
    seen: dict = {}
    for e in events:
        if e.get("type") != "dispatch":
            continue
        rec = e.get("record") or {}
        key = (rec.get("engine"), rec.get("why"),
               tuple(rec.get("fallback_chain") or ()))
        if key in seen:
            seen[key]["verdicts"] += e.get("verdicts") or 1
        else:
            seen[key] = {"rec": rec,
                         "verdicts": e.get("verdicts") or 1}
    if not seen:
        return ""
    rows = []
    for (eng, why, fb), info in seen.items():
        rec = info["rec"]
        pl = rec.get("plan") or {}
        # record-level pack fields are what actually ran; the plan's
        # are the intent (they differ when a native error degraded)
        pb = rec.get("pack_backend") or pl.get("pack_backend")
        pt = rec.get("pack_threads", pl.get("pack_threads"))
        pack = f"{pb} ×{pt}" if pb and pt else (pb or "")
        # deep mask-plane provenance (ISSUE 10): record-level fields
        # are what actually ran (e.g. a forced hypercube), the plan's
        # are the route
        dv = rec.get("deep_variant") or pl.get("deep_variant")
        dsh = rec.get("shards", pl.get("shards"))
        deep = f"{dv} ×{dsh}" if dv and dsh else (dv or "")
        rows.append(
            "<tr>"
            f"<td>{html.escape(str(eng))}</td>"
            f"<td>{html.escape(str(why or ''))}</td>"
            f"<td>{html.escape(' → '.join(fb))}</td>"
            f"<td>{html.escape(str(pl.get('bucket') or ''))}</td>"
            f"<td>{html.escape(pack)}</td>"
            f"<td>{html.escape(deep)}</td>"
            f"<td>{info['verdicts']}</td></tr>")
    return ("<h2>Dispatch plans</h2>"
            "<table><tr><th>Engine</th><th>Why</th>"
            "<th>Fallback chain</th><th>Bucket</th><th>Pack</th>"
            "<th>Deep shard</th><th>Verdicts</th></tr>"
            + "".join(rows) + "</table>")


# ---------------------------------------------------------------------------
# Causal flight recorder (ISSUE 19): /trace index, per-run flag list,
# and the /trace/<name>/<ts>/<trace_id> waterfall with the detection-
# lag decomposition and the cross-worker handoff link shaded.
# ---------------------------------------------------------------------------

_SEGMENT_COLORS = {"fsync": "#B8D4F0", "frame": "#A8E6CF",
                   "ack": "#FFE9A8", "window": "#F5C6A0",
                   "dispatch": "#E0B8F0", "flag": "#F3BBBC"}


def _trace_events(d: Path) -> list:
    from jepsen_tpu import telemetry
    p = d / "trace-index.jsonl"
    if not p.exists():
        return []
    try:
        return telemetry.read_events(p)
    except Exception:  # noqa: BLE001 - a torn index renders empty
        return []


def _ingest_span_stamps(tenant: str, seq) -> tuple:
    """(fs, recv, synced) for one streamed record, joined at render
    time from every ingest server journal under store/ingest/ — the
    copy that survives the worker that measured them being SIGKILLed
    (the takeover survivor's flag page still renders the full chain)."""
    from jepsen_tpu import telemetry
    fs = recv = synced = None
    root = store.BASE / "ingest"
    if not isinstance(seq, int) or not root.is_dir():
        return fs, recv, synced
    for p in sorted(root.glob("*.jsonl")):
        try:
            evs = telemetry.read_events(p)
        except Exception:  # noqa: BLE001 - skip torn journals
            continue
        for ev in evs:
            if ev.get("type") != "ingest-span" \
                    or ev.get("tenant") != tenant:
                continue
            for mark in ev.get("marks") or []:
                if isinstance(mark, list) and len(mark) == 2 \
                        and mark[0] == seq and fs is None:
                    fs = mark[1]
            lo, hi = ev.get("lo"), ev.get("hi")
            if isinstance(lo, int) and isinstance(hi, int) \
                    and lo <= seq < hi:
                recv = ev.get("recv") if recv is None else recv
                synced = ev.get("synced") if synced is None \
                    else synced
    return fs, recv, synced


def _resolve_segments(name: str, ts: str, rec: dict) -> dict:
    """The record's segment decomposition, re-derived after joining
    any transport stamps the emitting worker lacked (its in-memory
    stamps died with it; the ingest journal's copy did not)."""
    from jepsen_tpu import trace as trace_mod
    stamps = dict(rec.get("stamps") or {})
    if any(stamps.get(k) is None for k in ("fs", "recv", "synced")):
        fs, recv, synced = _ingest_span_stamps(f"{name}/{ts}",
                                               rec.get("seq"))
        for k, v in (("fs", fs), ("recv", recv), ("synced", synced)):
            if stamps.get(k) is None and v is not None:
                stamps[k] = v
    segs = trace_mod.lag_segments(stamps)
    return segs if segs is not None else (rec.get("segments") or {})


def trace_index_html(slowest: int = 0) -> bytes:
    rows = []
    for name, stamps in sorted(store.tests().items()):
        for ts in sorted(stamps, reverse=True):
            d = store.BASE / store._sanitize(name) / ts
            evs = _trace_events(d)
            if not evs:
                continue
            flags = [e for e in evs if e.get("type") == "trace-flag"]
            links = [e for e in evs if e.get("type") == "trace-link"]
            worst = max((e.get("lag_s") or 0.0 for e in flags),
                        default=0.0)
            rows.append((worst, name, ts, len(flags), len(links)))
    rows.sort(reverse=True)
    if slowest:
        rows = rows[:slowest]
    body = ("<h1>Traces</h1>"
            "<p><a href='/'>&larr; tests</a> &middot; "
            "<a href='/live'>live</a> &middot; "
            "<a href='/metrics'>metrics</a></p>"
            "<table><tr><th>Test</th><th>Run</th><th>Flags traced</th>"
            "<th>Handoff links</th><th>Worst lag (s)</th></tr>"
            + "".join(
                f"<tr><td>{html.escape(n)}</td>"
                f"<td><a href='/trace/{quote(n)}/{quote(t)}'>"
                f"{html.escape(t)}</a></td>"
                f"<td>{nf}</td><td>{nl}</td><td>{w:.4f}</td></tr>"
                for w, n, t, nf, nl in rows)
            + "</table>")
    if not rows:
        body += ("<p>(no traced flags yet — run with "
                 "<code>trace: true</code> under a serve-checker)</p>")
    return _page("Traces", body)


def trace_run_html(name: str, ts: str) -> bytes:
    d = _safe_path(f"{name}/{ts}")
    evs = _trace_events(d)
    flags = [e for e in evs if e.get("type") == "trace-flag"]
    links = [e for e in evs if e.get("type") == "trace-link"]
    base = f"/trace/{quote(name)}/{quote(ts)}"
    body = [f"<h1>{html.escape(name)} / {html.escape(ts)} "
            "&mdash; traces</h1>",
            "<p><a href='/trace'>&larr; traces</a> &middot; "
            f"<a href='/live/{quote(name)}/{quote(ts)}'>live</a> "
            "&middot; "
            f"<a href='/files/{quote(name)}/{quote(ts)}/"
            "trace-index.jsonl'>raw index</a></p>"]
    if links:
        body.append(
            "<h2>Cross-worker handoffs</h2><table><tr>"
            "<th>From</th><th>Epoch</th><th>To</th><th>Epoch</th>"
            "<th>Resume span</th><th>Silent (s)</th></tr>"
            + "".join(
                f"<tr style='background:{UNKNOWN_COLOR}'>"
                f"<td>{html.escape(str(lk.get('from_worker')))}</td>"
                f"<td>{html.escape(str(lk.get('from_epoch')))}</td>"
                f"<td>{html.escape(str(lk.get('to_worker')))}</td>"
                f"<td>{html.escape(str(lk.get('to_epoch')))}</td>"
                f"<td><code>{html.escape(str(lk.get('resume_span')))}"
                "</code></td>"
                f"<td>{lk.get('silent_s')}</td></tr>"
                for lk in links)
            + "</table>")
    body.append(
        "<h2>Traced flags</h2><table><tr><th>Trace</th><th>Lane</th>"
        "<th>Op</th><th>Event</th><th>Lag (s)</th>"
        "<th>Dominant segment</th><th>Worker</th></tr>"
        + "".join(
            f"<tr><td><a href='{base}/{quote(str(f.get('trace_id')))}'>"
            f"<code>{html.escape(str(f.get('trace_id'))[:16])}&hellip;"
            "</code></a></td>"
            f"<td>{html.escape(str(f.get('lane')))}</td>"
            f"<td>{html.escape(str(f.get('op_index')))}</td>"
            f"<td>{html.escape(str(f.get('event')))}</td>"
            f"<td>{f.get('lag_s')}</td>"
            f"<td>{html.escape(str(f.get('dominant')))}</td>"
            f"<td>{html.escape(str(f.get('worker')))}</td></tr>"
            for f in flags)
        + "</table>")
    if not flags:
        body.append("<p>(no traced flags in this run)</p>")
    return _page(f"traces {name}/{ts}", "".join(body))


def trace_flag_html(name: str, ts: str, trace_id: str) -> bytes:
    d = _safe_path(f"{name}/{ts}")
    evs = _trace_events(d)
    recs = [e for e in evs if e.get("type") == "trace-flag"
            and str(e.get("trace_id")) == trace_id]
    if not recs:
        raise FileNotFoundError(trace_id)
    links = [e for e in evs if e.get("type") == "trace-link"]
    body = [f"<h1>trace <code>{html.escape(trace_id[:16])}&hellip;"
            f"</code> &mdash; {html.escape(name)} / {html.escape(ts)}"
            "</h1>",
            f"<p><a href='/trace/{quote(name)}/{quote(ts)}'>"
            "&larr; run traces</a></p>"]
    for rec in recs:
        segs = _resolve_segments(name, ts, rec)
        lag = rec.get("lag_s")
        total = sum(v for v in segs.values()
                    if isinstance(v, (int, float))) if segs else 0.0
        body.append(
            f"<h2>flag: {html.escape(str(rec.get('event')))} on lane "
            f"{html.escape(str(rec.get('lane')))} (op "
            f"{html.escape(str(rec.get('op_index')))})</h2>"
            f"<p>span <code>{html.escape(str(rec.get('span')))}</code>"
            f" &middot; worker {html.escape(str(rec.get('worker')))}"
            f" (epoch {html.escape(str(rec.get('epoch')))})"
            f" &middot; context from "
            f"{html.escape(str(rec.get('ctx_source')))}"
            f" &middot; dispatch "
            f"{html.escape(str(rec.get('dispatch_id')))}</p>")
        # the handoff gap, shaded, between the dead worker's last
        # span and this record's parent (the survivor's resume span)
        for lk in links:
            if lk.get("resume_span") == rec.get("parent"):
                body.append(
                    f"<p style='background:{UNKNOWN_COLOR};"
                    "padding:.5em'>cross-worker handoff: "
                    f"<b>{html.escape(str(lk.get('from_worker')))}</b>"
                    f" (epoch {html.escape(str(lk.get('from_epoch')))}"
                    f", span <code>"
                    f"{html.escape(str(lk.get('from_span')))}</code>)"
                    " &rarr; "
                    f"<b>{html.escape(str(lk.get('to_worker')))}</b>"
                    f" resume span <code>"
                    f"{html.escape(str(lk.get('resume_span')))}</code>"
                    f" after {lk.get('silent_s')}s of silence</p>")
        if segs and total > 0:
            bars = "".join(
                f"<td style='background:"
                f"{_SEGMENT_COLORS.get(seg, '#EAEAEA')};width:"
                f"{max(int(600 * (segs.get(seg) or 0) / total), 1)}px'"
                f" title='{html.escape(seg)}: {segs.get(seg)}s'>"
                "</td>"
                for seg in _SEGMENT_COLORS)
            body.append(
                "<table><tr>" + bars + "</tr></table>"
                "<table><tr><th>Segment</th><th>Seconds</th>"
                "<th>Share</th></tr>"
                + "".join(
                    f"<tr><td style='background:"
                    f"{_SEGMENT_COLORS.get(seg, '#EAEAEA')}'>"
                    f"{html.escape(seg)}</td>"
                    f"<td>{segs.get(seg)}</td>"
                    f"<td>{100.0 * (segs.get(seg) or 0) / total:.1f}%"
                    "</td></tr>"
                    for seg in _SEGMENT_COLORS)
                + "</table>")
            if isinstance(lag, (int, float)) and lag > 0:
                pct = abs(total - lag) / lag * 100.0
                body.append(
                    f"<p>segments sum to {total:.6f}s vs measured "
                    f"flag lag {lag}s ({pct:.1f}% apart)</p>")
        stamps = rec.get("stamps") or {}
        body.append(
            "<h3>stamps</h3><table>"
            + "".join(
                f"<tr><th>{html.escape(k)}</th>"
                f"<td>{stamps.get(k)}</td></tr>"
                for k in ("w", "fs", "recv", "synced", "win",
                          "dis_s", "flag") if k in stamps)
            + "</table>")
    return _page(f"trace {trace_id[:16]}", "".join(body))


def metrics_text() -> str:
    """/metrics: the process exposition — federated with every fleet
    worker's exported snapshot (worker_id-labeled, stale-marked) when
    store/fleet/ sidecars exist.  Collisions resolve toward the
    federation: a supervisor's own registry says nothing useful about
    the workers doing the checking."""
    from jepsen_tpu import telemetry
    local = telemetry.snapshot()
    try:
        if not any((store.BASE / "fleet").glob("*.json")):
            return local
        fed = telemetry.federate(store.BASE)
    except Exception:  # noqa: BLE001 - federation must not break
        return local   # scraping the process metrics
    if not fed:
        return local
    fed_names = {ln.split()[2] for ln in fed.splitlines()
                 if ln.startswith("# TYPE ")}
    keep, skip = [], False
    for ln in local.splitlines():
        if ln.startswith("# TYPE "):
            skip = ln.split()[2] in fed_names
        if not skip:
            keep.append(ln)
    return fed + "\n".join(keep) + ("\n" if keep else "")


def zip_bytes(name: str, ts: str) -> bytes:
    d = _safe_path(f"{name}/{ts}")
    buf = io.BytesIO()
    with zipfile.ZipFile(buf, "w", zipfile.ZIP_DEFLATED) as z:
        for f in sorted(d.rglob("*")):
            if f.is_file():
                z.write(f, f.relative_to(d.parent))
    return buf.getvalue()


_CONTENT_TYPES = {".json": "application/json", ".txt": "text/plain",
                  ".log": "text/plain", ".jsonl": "text/plain",
                  ".html": "text/html", ".png": "image/png",
                  ".svg": "image/svg+xml"}


class Handler(BaseHTTPRequestHandler):
    def log_message(self, *a):  # quiet
        pass

    def _send(self, code: int, body: bytes,
              ctype: str = "text/html; charset=utf-8",
              extra: dict = None) -> None:
        self.send_response(code)
        self.send_header("Content-Type", ctype)
        self.send_header("Content-Length", str(len(body)))
        for k, v in (extra or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):  # noqa: N802
        try:
            path = self.path.split("?", 1)[0]
            if path == "/" or path == "":
                return self._send(200, home_html())
            if path == "/metrics":
                return self._send(200, metrics_text().encode(),
                                  "text/plain; version=0.0.4; "
                                  "charset=utf-8")
            if path == "/trace" or path == "/trace/":
                return self._send(200, trace_index_html())
            if path.startswith("/trace/"):
                parts = [unquote(x) for x in
                         path[len("/trace/"):].strip("/").split("/")]
                if len(parts) == 2:
                    return self._send(200, trace_run_html(*parts))
                if len(parts) == 3:
                    return self._send(200, trace_flag_html(*parts))
                return self._send(404, b"not found", "text/plain")
            if path == "/fleet" or path == "/fleet/":
                return self._send(200, fleet_html())
            if path == "/ingest" or path == "/ingest/":
                return self._send(200, ingest_html())
            if path == "/live" or path == "/live/":
                return self._send(200, live_index_html())
            if path.startswith("/live/"):
                parts = [unquote(x) for x in
                         path[len("/live/"):].strip("/").split("/")]
                if len(parts) == 2:
                    return self._send(200, live_run_html(*parts))
                return self._send(404, b"not found", "text/plain")
            if path == "/campaign" or path == "/campaign/":
                return self._send(200, campaign_index_html())
            if path.startswith("/campaign/"):
                parts = [unquote(x) for x in
                         path[len("/campaign/"):].strip("/").split("/")]
                if len(parts) == 1:
                    return self._send(200, campaign_html(parts[0]))
                return self._send(404, b"not found", "text/plain")
            if path == "/telemetry" or path == "/telemetry/":
                return self._send(200, telemetry_index_html())
            if path.startswith("/telemetry/"):
                parts = [unquote(x) for x in
                         path[len("/telemetry/"):].strip("/").split("/")]
                if len(parts) == 2:
                    return self._send(200, telemetry_run_html(*parts))
                return self._send(404, b"not found", "text/plain")
            if path.startswith("/elle/"):
                parts = [unquote(x) for x in
                         path[len("/elle/"):].strip("/").split("/")]
                if len(parts) == 2:
                    return self._send(200, elle_html(*parts))
                return self._send(404, b"not found", "text/plain")
            if path.startswith("/files/"):
                rel = unquote(path[len("/files/"):])
                p = _safe_path(rel)
                if p.is_dir():
                    return self._send(200, dir_html(rel, p))
                if p.is_file():
                    ctype = _CONTENT_TYPES.get(p.suffix,
                                               "application/octet-stream")
                    return self._send(200, p.read_bytes(), ctype)
                return self._send(404, b"not found", "text/plain")
            if path.startswith("/zip/"):
                parts = [unquote(x) for x in
                         path[len("/zip/"):].strip("/").split("/")]
                if len(parts) == 2:
                    data = zip_bytes(*parts)
                    fname = f"{parts[0]}-{parts[1]}.zip"
                    return self._send(
                        200, data, "application/zip",
                        {"Content-Disposition":
                         f"attachment; filename=\"{fname}\""})
            return self._send(404, b"not found", "text/plain")
        except PermissionError:
            return self._send(403, b"forbidden", "text/plain")
        except (FileNotFoundError, NotADirectoryError):
            return self._send(404, b"not found", "text/plain")
        except Exception as e:  # pragma: no cover
            return self._send(500, str(e).encode(), "text/plain")


def serve(host: str = "0.0.0.0", port: int = 8080, block: bool = True):
    """Start the dashboard (web.clj serve! :336).  Non-blocking mode
    returns the server; call .shutdown() to stop."""
    srv = ThreadingHTTPServer((host, port), Handler)
    if block:
        print(f"Serving store on http://{host}:{srv.server_address[1]}/")
        try:
            srv.serve_forever()
        finally:
            srv.server_close()
        return srv
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    return srv
