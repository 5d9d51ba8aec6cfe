"""YAML experiment files → CompiledExperiment (the engine-neutral artifact).

The reference's experiment file is XML: <topology> (GraphML), <host> specs
with quantity/bandwidth/start times, and plugin args
(src/main/core/support/configuration.c). This YAML schema preserves those
concepts — network / hosts / app / engine sections — and adds the
`engine.scheduler: cpu|tpu|sharded` selector mandated by BASELINE.json
("CPU and TPU engines are selected from the same config file").

Schema:

    general:
      seed: 1
      stop_time: 60 s            # durations: "<num> <ns|us|ms|s>" or int ns
    engine:
      scheduler: tpu             # cpu | tpu | sharded
      ev_cap: 256                # any EngineParams field
    network:
      graphml: path.graphml      # or:
      single_vertex: {latency: 10 ms, loss: 0.01}
    hosts:                       # expanded in order into host ids 0..H-1
      - name: relay
        count: 8
        vertex: 0                # attachment PoP: an int id or a GraphML node
                                 # id (the whole group on one vertex);
                                 # "spread" = host i of the group on vertex
                                 # i mod V, over ALL vertices in file order;
                                 # {spread: {countrycode: X}} = the same deal
                                 # over the vertices whose GraphML
                                 # ``countrycode`` is X (Shadow's
                                 # countrycodehint, without a draw)
        bandwidth_up: 100 Mbit   # "<num> <bit|Kbit|Mbit|Gbit>"/s
        bandwidth_down: 100 Mbit
    app:
      model: tgen                # tgen|tor|bitcoin|filexfer|dgram|phold
      params: {...}              # global scalars (engine-level knobs)
      defaults: {...}            # per-host params, broadcast to all hosts
      groups:                    # per-host params, per host group
        relay: {...}
    faults:                      # deterministic fault plane (fault/schedule.py;
      hosts:                     #   docs/SEMANTICS.md "Fault plane")
        - {group: relay, down_at: 2 s, up_at: 3 s}   # churn cycles
      links:
        - {src_vertex: pop_a, dst_vertex: pop_b, down_at: 4 s, up_at: 5 s}
      loss:
        - {src_vertex: pop_a, dst_vertex: pop_b, from: 6 s, until: 7 s,
           loss: 0.2}

Per-host values may be scalars or lists of length == group count. Durations
and bandwidths accept the unit strings above anywhere.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import numpy as np

from shadow1_tpu.config.compiled import CompiledExperiment
from shadow1_tpu.config.dns import Dns
from shadow1_tpu.config.topology import compile_paths, load_graphml
from shadow1_tpu.consts import MS, NS, SEC, US, EngineParams

_TIME_UNITS = {"ns": NS, "us": US, "ms": MS, "s": SEC, "sec": SEC}
_BW_UNITS = {"bit": 1, "kbit": 10**3, "mbit": 10**6, "gbit": 10**9}


def parse_time_ns(v) -> int:
    if isinstance(v, (int, float)) and not isinstance(v, bool):
        return int(v)
    s = str(v).strip().lower()
    parts = s.split()
    if len(parts) == 2 and parts[1] in _TIME_UNITS:
        return int(float(parts[0]) * _TIME_UNITS[parts[1]])
    for unit in ("ns", "us", "ms", "sec", "s"):
        if s.endswith(unit):
            return int(float(s[: -len(unit)]) * _TIME_UNITS[unit])
    return int(float(s))


def parse_bw_bits(v) -> int:
    if isinstance(v, (int, float)) and not isinstance(v, bool):
        return int(v)
    s = str(v).strip().lower().replace("/s", "")
    parts = s.split()
    if len(parts) == 2 and parts[1] in _BW_UNITS:
        return int(float(parts[0]) * _BW_UNITS[parts[1]])
    for unit in ("kbit", "mbit", "gbit", "bit"):
        if s.endswith(unit):
            return int(float(s[: -len(unit)]) * _BW_UNITS[unit])
    return int(float(s))


@dataclasses.dataclass
class HostGroup:
    name: str
    count: int
    start: int          # first global host id
    vertex_spec: Any
    bw_up: int
    bw_dn: int
    stop_time: int      # ns the host halts (churn); NO_STOP = never
    cpu_ns_per_event: int
    tx_qlen_bytes: int  # NIC uplink queue bound (0 = unbounded)
    rx_qlen_bytes: int
    aqm_min_bytes: int  # RED uplink AQM thresholds (aqm_max_bytes 0 = off)
    aqm_max_bytes: int
    aqm_pmax: float

    @property
    def ids(self) -> np.ndarray:
        return np.arange(self.start, self.start + self.count)


# Per-host app parameter schemas: name -> (dtype, default, parser).
# A parser of parse_time_ns lets YAML say "100 ms" for per-host times.
_T = parse_time_ns
_APP_PARAMS: dict[str, dict[str, tuple]] = {
    "filexfer": {
        "role": (np.int64, 2, None),
        "server": (np.int64, 0, None),
        "flow_bytes": (np.int64, 0, None),
        "start_time": (np.int64, 0, _T),
        "flow_count": (np.int64, 0, None),
    },
    "dgram": {
        "dst": (np.int64, 0, None),
        "payload": (np.int64, 0, None),
        "interval": (np.int64, 0, _T),
        "count": (np.int64, 0, None),
        "start_time": (np.int64, 0, _T),
    },
    "tgen": {
        "active": (np.int64, 0, None),
        "streams": (np.int64, 0, None),
        "mean_bytes": (np.float64, 0.0, None),
        "mean_think_ns": (np.float64, 0.0, _T),
        "start_time": (np.int64, 0, _T),
    },
    "tor": {
        "role": (np.int64, 3, None),
        "relay_weight": (np.int64, 0, None),
        "is_guard": (bool, False, None),
        "is_exit": (bool, False, None),
        "n_circuits": (np.int64, 0, None),
        "n_streams": (np.int64, 0, None),
        "mean_stream_cells": (np.float64, 0.0, None),
        "mean_think_ns": (np.float64, 0.0, _T),
        "start_time": (np.int64, 0, _T),
    },
    "bitcoin": {},  # graph-structured config passes through `params`
    "phold": {},
}


def _reject_unknown(section: str, have, allowed) -> None:
    """Unknown-key rejection, fault/schedule.py-style, for every config
    section: a typo like ``ev_capp:`` or ``stop_tme:`` must fail fast at
    load instead of silently running the experiment on defaults."""
    unknown = set(have) - set(allowed)
    assert not unknown, (
        f"unknown {section} keys: {sorted(map(str, unknown))} "
        f"(allowed: {sorted(allowed)})"
    )


# Host-group entry schema (the per-group knobs _expand_hosts reads).
_HOST_KEYS = ("name", "count", "vertex", "bandwidth_up", "bandwidth_down",
              "stop_time", "cpu_per_event", "tx_queue_bytes",
              "rx_queue_bytes", "aqm_min_bytes", "aqm_max_bytes", "aqm_pmax")


def _expand_hosts(spec: list[dict]) -> list[HostGroup]:
    from shadow1_tpu.config.compiled import NO_STOP

    groups, start = [], 0
    for g in spec:
        _reject_unknown(f"hosts[{g.get('name', start)}]", g, _HOST_KEYS)
        count = int(g.get("count", 1))
        groups.append(HostGroup(
            name=g["name"],
            count=count,
            start=start,
            vertex_spec=g.get("vertex", 0),
            bw_up=parse_bw_bits(g.get("bandwidth_up", "1 Gbit")),
            bw_dn=parse_bw_bits(g.get("bandwidth_down", "1 Gbit")),
            stop_time=(
                parse_time_ns(g["stop_time"]) if "stop_time" in g else NO_STOP
            ),
            cpu_ns_per_event=(
                parse_time_ns(g["cpu_per_event"]) if "cpu_per_event" in g else 0
            ),
            tx_qlen_bytes=int(g.get("tx_queue_bytes", 0)),
            rx_qlen_bytes=int(g.get("rx_queue_bytes", 0)),
            aqm_min_bytes=int(g.get("aqm_min_bytes", 0)),
            aqm_max_bytes=int(g.get("aqm_max_bytes", 0)),
            aqm_pmax=float(g.get("aqm_pmax", 0.1)),
        ))
        start += count
    return groups


def _vertices_with_code(group: str, spec: dict, codes) -> np.ndarray:
    """The vertices, in file order, that a group's ``vertex: {spread:
    {countrycode: X}}`` deals its hosts over."""
    hint = spec.get("spread")
    assert set(spec) == {"spread"} and isinstance(hint, dict) \
        and set(hint) == {"countrycode"}, (
            f"hosts[{group}].vertex: a mapping must be "
            f"{{spread: {{countrycode: <code>}}}}, not {spec!r}")
    code = str(hint["countrycode"])
    where = np.flatnonzero([c == code for c in codes])
    have = sorted({c for c in codes if c is not None})
    assert where.size, (
        f"hosts[{group}].vertex: no vertex has countrycode {code!r} "
        f"(the topology's vertices carry {have or 'no countrycode'})")
    return where


def _vertex_assignment(groups, vertex_names, n_hosts, codes) -> np.ndarray:
    n_v = max(len(vertex_names), 1)
    name_idx = {str(n): i for i, n in enumerate(vertex_names)}
    hv = np.zeros(n_hosts, np.int32)
    for g in groups:
        if g.vertex_spec == "spread":
            hv[g.start:g.start + g.count] = np.arange(g.count) % n_v
        elif isinstance(g.vertex_spec, dict):
            among = _vertices_with_code(g.name, g.vertex_spec, codes)
            hv[g.start:g.start + g.count] = among[
                np.arange(g.count) % among.size]
        elif isinstance(g.vertex_spec, int):
            hv[g.start:g.start + g.count] = g.vertex_spec
        else:
            hv[g.start:g.start + g.count] = name_idx[str(g.vertex_spec)]
    assert hv.max(initial=0) < n_v, "host attached to missing vertex"
    return hv


def _per_host_array(name, dtype, default, parser, groups, defaults, group_cfg, h):
    arr = np.full(h, default, dtype)
    conv = parser or (lambda x: x)
    if name in defaults:
        arr[:] = _group_values(name, defaults[name], conv, h, np.arange(h))
    for g in groups:
        block = group_cfg.get(g.name, {})
        if name in block:
            val = block[name]
            arr[g.ids] = _group_values(name, val, conv, g.count,
                                       np.arange(g.count))
    return arr


def _group_values(name, val, conv, count, idx):
    """One app-param value spec → per-host values for a group of ``count``.

    Three forms: a scalar (broadcast), a list (one per host), or a stagger
    dict ``{start: X, interval: Y}`` → ``start + i·interval`` for host i in
    the group — the idiom for spreading e.g. client bootstrap times so a
    10k-client rung does not burst every dirauth in one window (the
    reference's example configs stagger client start times the same way)."""
    if isinstance(val, dict):
        extra = set(val) - {"start", "interval"}
        assert not extra, f"unknown stagger keys for {name}: {extra}"
        return conv(val.get("start", 0)) + idx * conv(val.get("interval", 0))
    if isinstance(val, list):
        assert len(val) == count, (name, count)
        return [conv(x) for x in val]
    return conv(val)


def _ring_chord_peers(h: int, k: int) -> np.ndarray:
    """Ring ±1 plus power-of-4 chords: a circulant, the same for every h."""
    chords = [1]
    while len(chords) < k // 2:
        chords.append(chords[-1] * 4)
    peers = np.zeros((h, k), np.int32)
    for ci, c in enumerate(chords):
        peers[:, 2 * ci] = (np.arange(h) - c) % h
        peers[:, 2 * ci + 1] = (np.arange(h) + c) % h
    return peers


def _random_regular_peers(h: int, k: int, seed: int) -> np.ndarray:
    """A random simple connected k-regular graph on h nodes as a ``[h, k]``
    table, each row its node's neighbours in ascending order.

    The pairing model (a random perfect matching of the h·k half-edges),
    then every self-loop and double edge is switched against an edge drawn
    at random ((u, v), (x, y) → (u, x), (v, y), redrawn until neither new
    edge is a loop or already there): near uniform for k ≪ h (McKay &
    Wormald 1990). The whole draw is made again until the graph is
    connected, which at k ≥ 3 it nearly always is the first time. Every
    draw comes from ``np.random.RandomState(seed)``, whose stream numpy has
    frozen: one (h, k, seed) is one table, here and in ten years."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import connected_components

    assert 0 < k < h and (h * k) % 2 == 0, (
        f"graph.kind random_regular: no simple {k}-regular graph on {h} nodes")
    rs = np.random.RandomState(seed)

    def key(a, b):
        return (a, b) if a < b else (b, a)

    while True:
        stubs = rs.permutation(np.repeat(np.arange(h), k)).tolist()
        edges, have, bad = [], set(), []
        for u, v in zip(stubs[0::2], stubs[1::2]):
            if u == v or key(u, v) in have:
                bad.append((u, v))
            else:
                have.add(key(u, v))
                edges.append((u, v))
        for u, v in bad:
            for _ in range(64 * k):
                i = int(rs.randint(len(edges)))
                x, y = edges[i] if rs.randint(2) else edges[i][::-1]
                new = {key(u, x), key(v, y)}
                if u != x and v != y and len(new) == 2 and not new & have:
                    break
            else:
                break       # no switch found (a graph near complete): redraw
            have.remove(key(x, y))
            have |= new
            edges[i] = (u, x)
            edges.append((v, y))
        if len(edges) != h * k // 2:
            continue
        a, b = np.asarray(edges, np.int32).T
        src, dst = np.concatenate([a, b]), np.concatenate([b, a])
        adj = coo_matrix((np.ones(src.size, np.int8), (src, dst)), shape=(h, h))
        if connected_components(adj, directed=False)[0] == 1:
            order = np.lexsort((dst, src))
            return dst[order].reshape(h, k)


_GRAPH_KINDS = ("ring_chord", "random_regular")


def _gen_bitcoin_cfg(model_cfg: dict, h: int, seed: int) -> None:
    """Expand bitcoin's generator specs into concrete arrays.

    ``graph: {kind: ring_chord, k: K}`` (the default) → symmetric K-regular
    peer graph (ring ±1 plus power-of-4 chords); ``graph: {kind:
    random_regular, k: K, seed: S}`` → a random simple connected K-regular
    graph drawn from ``S`` alone, not from ``general.seed``: every lane of a
    seed study peers alike (the fleet refuses lanes that differ in
    ``peers``). ``tx: {count, start, interval}`` → staggered transactions at
    config-RNG-chosen origins. Explicit ``peers`` / ``tx_origin`` /
    ``tx_time`` arrays may be given instead.
    """
    if "peers" not in model_cfg:
        gspec = model_cfg.pop("graph", {})
        _reject_unknown("app.params.graph", gspec, ("kind", "k", "seed"))
        kind = gspec.get("kind", "ring_chord")
        assert kind in _GRAPH_KINDS, (
            f"unknown app.params.graph.kind {kind!r} (known: {_GRAPH_KINDS})")
        k = int(gspec.get("k", 8))
        if kind == "random_regular":
            model_cfg["peers"] = _random_regular_peers(
                h, k, int(gspec.get("seed", 0)))
        else:
            assert "seed" not in gspec, \
                "app.params.graph.seed: ring_chord draws nothing"
            assert k % 2 == 0 and k >= 2
            model_cfg["peers"] = _ring_chord_peers(h, k)
    if "tx_origin" not in model_cfg:
        tspec = model_cfg.pop("tx", {})
        count = int(tspec.get("count", 50))
        start = parse_time_ns(tspec.get("start", "1 s"))
        interval = parse_time_ns(tspec.get("interval", "200 ms"))
        # Config-gen only. A seed under 2**32 seeds the generator as it
        # always has; a wider one (a study's pool, the benchmark's seeds)
        # goes in whole as two 32-bit words, so no two seeds share a stream.
        s = int(seed) ^ 0xB17C01
        rs = np.random.RandomState(
            s if s < 1 << 32 else [s & 0xFFFFFFFF, s >> 32])
        model_cfg["tx_origin"] = rs.randint(0, h, count).astype(np.int64)
        model_cfg["tx_time"] = (start + np.arange(count) * interval).astype(np.int64)


class WatchlistError(ValueError):
    """A probe watchlist entry (``probes:`` section / ``--watch``) failed to
    resolve: unknown host/group name, bad index, or out-of-range socket.
    Raised at CONFIG time — the CLI maps it onto the standard structured
    config error path (``ap.error`` → EXIT_CONFIG) so a typo'd target can
    never reach the traced engine as a shape crash."""


def _resolve_probe_host(spec, dns) -> int:
    """One watchlist host spec → global host id.

    Accepted forms: an int host id; ``"name"`` / ``"@name"`` (any hostname
    or group name the Dns registry knows — bare group name = its first
    host); ``"name[i]"`` (the group's i-th host, via the registry's
    ``name-i`` convention)."""
    txt = str(spec).strip()
    if isinstance(spec, int) or txt.lstrip("-").isdigit():
        hid = int(txt)
        if not 0 <= hid < len(dns):
            raise WatchlistError(
                f"probe host id {hid} out of range (hosts 0..{len(dns) - 1})")
        return hid
    name = txt[1:] if txt.startswith("@") else txt
    if name.endswith("]") and "[" in name:
        base, _, idx_s = name[:-1].partition("[")
        try:
            idx = int(idx_s)
        except ValueError:
            raise WatchlistError(
                f"probe target {txt!r}: index {idx_s!r} is not an integer"
            ) from None
        name = base if (idx == 0 and f"{base}-0" not in dns._by_name) \
            else f"{base}-{idx}"
    try:
        return dns.resolve(name)
    except KeyError:
        import difflib

        close = difflib.get_close_matches(name, dns._by_name, n=3)
        hint = f" — did you mean {', '.join(map(repr, close))}?" if close \
            else ""
        raise WatchlistError(
            f"unknown probe target {txt!r}: no host or group by that "
            f"name{hint}") from None


def resolve_watchlist(entries, dns, sockets_per_host: int) -> tuple:
    """Watchlist specs → the EngineParams.probes tuple of (host, sock) ints.

    ``entries`` come from the ``probes:`` config section (list of
    ``"host[:sock]"`` strings, int ids, or ``{host:, sock:}`` dicts) or
    repeated ``--watch`` flags. sock defaults to −1 (the host-only
    NIC/event view). Duplicates collapse, first occurrence wins the order.
    Every failure raises WatchlistError with a typo-grade message."""
    if isinstance(entries, (str, int, dict)):
        entries = [entries]
    if not isinstance(entries, (list, tuple)):
        raise WatchlistError(
            f"probes: must be a list of host[:sock] targets, "
            f"got {type(entries).__name__}")
    probes: list[tuple[int, int]] = []
    for e in entries:
        if isinstance(e, dict):
            unknown = set(e) - {"host", "sock"}
            if unknown:
                raise WatchlistError(
                    f"unknown probe entry keys {sorted(map(str, unknown))} "
                    f"(allowed: host, sock)")
            if "host" not in e:
                raise WatchlistError(f"probe entry {e!r} is missing 'host'")
            spec, sock_s = e["host"], e.get("sock", -1)
        else:
            txt = str(e)
            spec, sep, tail = txt.rpartition(":")
            if not sep:
                spec, sock_s = txt, -1
            else:
                sock_s = tail
        host = _resolve_probe_host(spec, dns)
        try:
            sock = int(sock_s)
        except (TypeError, ValueError):
            raise WatchlistError(
                f"probe target {e!r}: socket {sock_s!r} is not an integer"
            ) from None
        if not -1 <= sock < sockets_per_host:
            raise WatchlistError(
                f"probe target {e!r}: socket {sock} out of range "
                f"(-1 = host view, else 0..{sockets_per_host - 1})")
        pr = (host, sock)
        if pr not in probes:
            probes.append(pr)
    return tuple(probes)


def resolve_edges(entries, vertex_names) -> tuple:
    """``"VS:VD"`` edge specs → tuple of (src_vertex, dst_vertex) int pairs.

    Each half is a topology vertex name (GraphML node id) or a numeric
    vertex id — the same namespace link records report. Used by the
    pcapdump ``--edge`` filter. Duplicates collapse, first occurrence wins
    the order. Every failure raises WatchlistError with a typo-grade
    message (the CLI maps it onto ap.error → EXIT_CONFIG, like the probe
    watchlist)."""
    names = [str(n) for n in (vertex_names or [])]
    idx = {n: i for i, n in enumerate(names)}
    n_v = len(names)

    def one(txt, whole):
        txt = txt.strip()
        if txt.lstrip("-").isdigit():
            v = int(txt)
            if not (0 <= v < n_v if n_v else v >= 0):
                raise WatchlistError(
                    f"edge {whole!r}: vertex id {v} out of range "
                    f"(vertices 0..{n_v - 1})")
            return v
        if txt in idx:
            return idx[txt]
        import difflib

        close = difflib.get_close_matches(txt, names, n=3)
        hint = f" — did you mean {', '.join(map(repr, close))}?" \
            if close else ""
        raise WatchlistError(
            f"edge {whole!r}: unknown vertex {txt!r}{hint}")

    edges: list[tuple[int, int]] = []
    for e in entries:
        txt = str(e)
        src, sep, dst = txt.partition(":")
        if not sep or not src.strip() or not dst.strip():
            raise WatchlistError(
                f"edge {txt!r}: expected SRC_VERTEX:DST_VERTEX")
        pr = (one(src, txt), one(dst, txt))
        if pr not in edges:
            edges.append(pr)
    return tuple(edges)


def build_experiment(doc: dict, base_dir: str = ".") -> tuple[CompiledExperiment, EngineParams, str]:
    """YAML document → (CompiledExperiment, EngineParams, scheduler)."""
    import os

    _reject_unknown("top-level config", doc,
                    ("general", "engine", "network", "hosts", "app",
                     "faults", "sweep", "probes"))
    # ``sweep:`` belongs to fleet mode (shadow1_tpu/fleet/expand.py): a solo
    # run of a sweep config runs the BASE experiment; its section schema is
    # validated there, at --fleet expansion time.
    gen = doc.get("general", {})
    _reject_unknown("general:", gen, ("seed", "stop_time"))
    seed = int(gen.get("seed", 1))
    end_time = parse_time_ns(gen.get("stop_time", "10 s"))

    # -- engine ------------------------------------------------------------
    eng = dict(doc.get("engine", {}))
    scheduler = eng.pop("scheduler", "tpu")
    fields = {f.name: f for f in dataclasses.fields(EngineParams)}
    unknown = set(eng) - set(fields)
    assert not unknown, f"unknown engine params: {unknown}"
    # The probe watchlist is NOT an engine: knob — it needs host-name
    # resolution (the top-level ``probes:`` section / --watch own it), and
    # the scalar coercion below would mangle the target list anyway.
    assert "probes" not in eng, (
        "engine.probes is not settable — use the top-level 'probes:' "
        "section (host[:sock] targets) or the --watch flag"
    )
    # Coerce by the DECLARED field type (a quoted "256" in YAML must still
    # become an int; only genuinely-str fields like on_overflow stay str).
    params = EngineParams(**{
        k: str(v) if fields[k].type in (str, "str") else int(v)
        for k, v in eng.items()
    })

    # -- network -----------------------------------------------------------
    net = doc.get("network", {})
    _reject_unknown("network:", net, ("graphml", "single_vertex", "jitter"))
    if "single_vertex" in net:
        _reject_unknown("network.single_vertex:", net["single_vertex"],
                        ("latency", "loss"))
    if "graphml" in net:
        path = net["graphml"]
        if not os.path.isabs(path):
            path = os.path.join(base_dir, path)
        names, lat_e, loss_e, directed, prefer_direct, codes = \
            load_graphml(path)
        lat_vv, loss_vv = compile_paths(lat_e, loss_e, directed=directed,
                                        prefer_direct=prefer_direct)
    else:
        sv = net.get("single_vertex", {})
        names, codes = ["v0"], [None]
        lat_vv = np.full((1, 1), parse_time_ns(sv.get("latency", "10 ms")), np.int64)
        loss_vv = np.full((1, 1), float(sv.get("loss", 0.0)), np.float32)
    # Per-packet path-latency jitter amplitude (± ns), uniform over all
    # paths. (Per-edge graphml jitter attributes are NOT read yet — a
    # config must set network.jitter explicitly.)
    jitter = net.get("jitter")
    jitter_vv = (
        np.full_like(lat_vv, parse_time_ns(jitter)) if jitter is not None else None
    )

    # -- hosts -------------------------------------------------------------
    groups = _expand_hosts(doc.get("hosts", [{"name": "host", "count": 1}]))
    h = sum(g.count for g in groups)
    host_vertex = _vertex_assignment(groups, names, h, codes)
    bw_up = np.zeros(h, np.int64)
    bw_dn = np.zeros(h, np.int64)
    stop_time = np.zeros(h, np.int64)
    cpu_ns = np.zeros(h, np.int64)
    tx_qlen = np.zeros(h, np.int64)
    rx_qlen = np.zeros(h, np.int64)
    aqm_min = np.zeros(h, np.int64)
    aqm_max = np.zeros(h, np.int64)
    aqm_pmax = np.zeros(h, np.float64)
    for g in groups:
        bw_up[g.ids] = g.bw_up
        bw_dn[g.ids] = g.bw_dn
        stop_time[g.ids] = g.stop_time
        cpu_ns[g.ids] = g.cpu_ns_per_event
        tx_qlen[g.ids] = g.tx_qlen_bytes
        rx_qlen[g.ids] = g.rx_qlen_bytes
        aqm_min[g.ids] = g.aqm_min_bytes
        aqm_max[g.ids] = g.aqm_max_bytes
        aqm_pmax[g.ids] = g.aqm_pmax if g.aqm_max_bytes else 0.0

    # -- app ---------------------------------------------------------------
    appsec = doc.get("app", {"model": "phold"})
    _reject_unknown("app:", appsec, ("model", "params", "defaults", "groups"))
    app = appsec["model"]
    model_cfg: dict[str, Any] = dict(appsec.get("params", {}))
    schema = _APP_PARAMS.get(app)
    assert schema is not None, f"unknown app model {app!r}"

    # Group-name references: "@name" → first host id of that group (e.g.
    # filexfer's `server: "@server"`), resolved before array building.
    by_name = {g.name: g for g in groups}

    def resolve(tree):
        if isinstance(tree, str) and tree.startswith("@"):
            return by_name[tree[1:]].start
        if isinstance(tree, dict):
            return {k: resolve(v) for k, v in tree.items()}
        if isinstance(tree, list):
            return [resolve(v) for v in tree]
        return tree

    defaults = resolve(appsec.get("defaults", {}))
    group_cfg = resolve(appsec.get("groups", {}))
    model_cfg = resolve(model_cfg)
    # Typos fail loudly, like the engine section: every defaults/groups key
    # must name a schema parameter, every groups key a host group.
    if schema:
        allowed = set(schema)
        assert set(defaults) <= allowed, \
            f"unknown app.defaults params: {set(defaults) - allowed}"
        host_names = {g.name for g in groups}
        assert set(group_cfg) <= host_names, \
            f"unknown app.groups host groups: {set(group_cfg) - host_names}"
        for gname, block in group_cfg.items():
            assert set(block) <= allowed, \
                f"unknown params in app.groups.{gname}: {set(block) - allowed}"
    for pname, (dtype, default, parser) in schema.items():
        model_cfg[pname] = _per_host_array(
            pname, dtype, default, parser, groups, defaults, group_cfg, h
        )

    # -- faults ------------------------------------------------------------
    from shadow1_tpu.fault.schedule import parse_faults

    faults = parse_faults(doc.get("faults"), groups, names)

    if app == "bitcoin":
        _gen_bitcoin_cfg(model_cfg, h, seed)
    if app == "phold":
        model_cfg.setdefault("mean_delay_ns", float(10 * MS))
        model = "phold"
    else:
        model_cfg["app"] = app
        model = "net"

    exp = CompiledExperiment(
        n_hosts=h,
        seed=seed,
        end_time=end_time,
        lat_vv=lat_vv,
        loss_vv=loss_vv,
        host_vertex=host_vertex,
        bw_up=bw_up,
        bw_dn=bw_dn,
        model=model,
        model_cfg=model_cfg,
        jitter_vv=jitter_vv,
        stop_time=stop_time,
        cpu_ns_per_event=cpu_ns,
        tx_qlen_bytes=tx_qlen,
        rx_qlen_bytes=rx_qlen,
        aqm_min_bytes=aqm_min,
        aqm_max_bytes=aqm_max,
        aqm_pmax=aqm_pmax,
        faults=faults,
        dns=Dns.from_groups(groups, host_vertex),
        vertex_names=[str(n) for n in names],
    )
    exp.validate()
    # -- probes ------------------------------------------------------------
    # Flow-probe watchlist: resolved through the same name registry app
    # references use, landing as static (host, sock) int pairs in
    # EngineParams.probes (telemetry/probes.py samples them per window).
    watch = doc.get("probes")
    if watch is not None:
        params = dataclasses.replace(
            params,
            probes=resolve_watchlist(watch, exp.dns,
                                     params.sockets_per_host))
    return exp, params, scheduler


def load_experiment(path: str):
    """Load a YAML experiment file → (CompiledExperiment, EngineParams,
    scheduler)."""
    import os

    import yaml

    with open(path) as f:
        doc = yaml.safe_load(f)
    return build_experiment(doc, base_dir=os.path.dirname(os.path.abspath(path)))
