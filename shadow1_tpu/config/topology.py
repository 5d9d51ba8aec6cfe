"""GraphML topology loader + vertex-level path compilation.

The reference loads a GraphML network graph with igraph and answers
latency/reliability queries with lazily-cached Dijkstra runs
(src/main/routing/topology.c getLatency/getReliability). We instead compile
the whole graph ONCE on the host into dense vertex-level tensors
(SURVEY §7.1: exploit the vertex/host split — topologies have few network
vertices with many attached hosts):

* ``lat_vv``  — all-pairs latency (ns) along minimum-latency paths;
* ``loss_vv`` — end-to-end loss probability along those same paths
  (1 - Π(1-loss_e), the reference's per-edge reliability product).

A published Shadow/Tor topology file loads here unchanged, and this is what
is read of it (the reference's GraphML schema):

* edge ``latency`` (float, *milliseconds* — Shadow convention) or
  ``latency_ns``, and edge ``packetloss`` (probability; ``loss`` is taken for
  it). A self-loop gives the latency and loss between two hosts of its vertex;
* vertex ``countrycode``: what a host group's ``vertex: {spread:
  {countrycode: X}}`` places by (config/experiment.py; Shadow's
  ``countrycodehint``). Vertices are the points of presence hosts attach to;
* graph ``preferdirectpaths`` (Shadow's; "True"/"False"): an edge is the
  path between its two ends even where a detour is shorter, so a table of
  measured end-to-end latencies is read as it stands.

Every other attribute a file carries is IGNORED, and ``load_graphml`` says
so once, by name: an edge's ``jitter`` (``network.jitter`` in the experiment
file is the one jitter there is), a vertex's ``bandwidthup`` /
``bandwidthdown`` (a host group's ``bandwidth_up`` / ``bandwidth_down``
decide) and a vertex's ``packetloss``, ``ip``, ``citycode``, ``type`` and
whatever else.
"""

from __future__ import annotations

import warnings
from typing import NamedTuple

import numpy as np

from shadow1_tpu.consts import MS

# What is read of a file, by the element that carries it (the docstring above).
_EDGE_ATTRS = ("latency", "latency_ns", "packetloss", "loss")
_VERTEX_ATTRS = ("countrycode",)


class LoadedGraph(NamedTuple):
    """``load_graphml``'s result: the node id list (stable order), dense
    [V, V] edge matrices (np.inf / 0 where no edge), whether the graph is
    directed, whether it sets ``preferdirectpaths``, and each vertex's
    ``countrycode`` in the ids' order (None where a vertex has none)."""
    vertex_ids: list
    lat_e: np.ndarray
    loss_e: np.ndarray
    directed: bool
    prefer_direct: bool
    countrycodes: list


def load_graphml(path: str) -> LoadedGraph:
    """Read one GraphML topology (the module docstring says which
    attributes), warning once of those it carries and nothing reads.

    Directed GraphML (Shadow's published Tor topologies use
    edgedefault="directed" with possibly asymmetric latencies) keeps each
    direction separate; undirected input is symmetrized."""
    import networkx as nx

    g = nx.read_graphml(path)
    directed = g.is_directed()
    nodes = list(g.nodes())
    codes = [None if (c := g.nodes[n].get("countrycode")) is None else str(c)
             for n in nodes]
    ignored = sorted(
        {f"vertex {k}" for _, d in g.nodes(data=True) for k in d
         if k not in _VERTEX_ATTRS}
        | {f"edge {k}" for _, _, d in g.edges(data=True) for k in d
           if k not in _EDGE_ATTRS})
    if ignored:
        warnings.warn(f"{path}: attributes carried and not read: "
                      f"{', '.join(ignored)} (config/topology.py says what is)",
                      stacklevel=2)
    index = {n: i for i, n in enumerate(nodes)}
    v = len(nodes)
    lat = np.full((v, v), np.inf)
    loss = np.zeros((v, v))
    for a, b, data in g.edges(data=True):
        if "latency_ns" in data:
            l_ns = float(data["latency_ns"])
        elif "latency" in data:
            l_ns = float(data["latency"]) * MS  # Shadow: milliseconds
        else:
            raise ValueError(f"edge {a}-{b} missing latency attribute")
        p = float(data.get("packetloss", data.get("loss", 0.0)))
        i, j = index[a], index[b]
        # Self-loop edges (Shadow convention) give the intra-PoP latency and
        # loss of hosts attached to the same vertex.
        lat[i, j] = l_ns
        loss[i, j] = p
        if not directed:
            lat[j, i] = l_ns
            loss[j, i] = p
    prefer = str(g.graph.get("preferdirectpaths", "false")).lower()
    if prefer not in ("true", "false"):
        raise ValueError(f"{path}: preferdirectpaths must be True or False, "
                         f"not {prefer!r}")
    return LoadedGraph(nodes, lat, loss, directed, prefer == "true", codes)


def compile_paths(lat_e: np.ndarray, loss_e: np.ndarray,
                  self_latency_ns: int | None = None,
                  directed: bool = False,
                  prefer_direct: bool = False) -> tuple[np.ndarray, np.ndarray]:
    """All-pairs min-latency paths → (lat_vv i64 ns, loss_vv f32).

    With ``prefer_direct`` two vertices joined by an edge use that edge's
    latency and loss, whatever a detour would give.

    Loss accumulates along each chosen latency-shortest path via the
    predecessor matrix (vectorized back-walk, ≤V steps). The diagonal
    (same-vertex host pairs) uses the vertex's GraphML self-loop latency
    where present, else ``self_latency_ns``, else the minimum edge latency —
    it must stay positive, since the conservative window is min(lat_vv)
    (the reference computes runahead the same way, src/main/core/master.c).
    Its loss is the self-loop's ``packetloss`` where the vertex has a
    self-loop (both attributes of that edge are the same-vertex pair's, as
    ``network.single_vertex: {latency, loss}`` always gave), else 0.
    """
    from scipy.sparse.csgraph import dijkstra

    v = lat_e.shape[0]
    self_lat = np.diag(lat_e).copy()          # self-loops (inf = absent)
    self_loss = np.where(np.isfinite(self_lat), np.diag(loss_e), 0.0)
    lat_e = lat_e.copy()
    np.fill_diagonal(lat_e, np.inf)
    finite = lat_e[np.isfinite(lat_e)]
    min_edge = float(finite.min()) if finite.size else float(self_latency_ns or 0)
    default_self = self_latency_ns if self_latency_ns is not None else min_edge
    self_lat = np.where(np.isfinite(self_lat), self_lat, default_self)
    assert (self_lat > 0).all(), "intra-vertex latency must be positive"
    graph = np.where(np.isinf(lat_e), 0.0, lat_e)
    dist, pred = dijkstra(
        graph, directed=directed, return_predecessors=True, unweighted=False
    )
    if np.isinf(dist).any():
        bad = int(np.isinf(dist).sum())
        raise ValueError(f"topology is disconnected ({bad} unreachable pairs)")
    # Walk predecessors for all (src, dst) pairs at once, multiplying edge
    # reliability (1 - loss) per hop.
    rel = np.ones((v, v))
    rel_e = 1.0 - loss_e
    src = np.broadcast_to(np.arange(v)[:, None], (v, v)).copy()
    cur = np.broadcast_to(np.arange(v)[None, :], (v, v)).copy()
    for _ in range(v):
        prev = pred[src, cur]
        active = prev >= 0
        if not active.any():
            break
        p_safe = np.where(active, prev, 0)
        rel *= np.where(active, rel_e[p_safe, cur], 1.0)
        cur = np.where(active, p_safe, cur)
    if prefer_direct:
        direct = np.isfinite(lat_e)
        dist = np.where(direct, lat_e, dist)
        rel = np.where(direct, rel_e, rel)
    lat_vv = np.rint(dist).astype(np.int64)
    np.fill_diagonal(lat_vv, np.rint(self_lat).astype(np.int64))
    loss_vv = (1.0 - rel).astype(np.float32)
    np.fill_diagonal(loss_vv, self_loss.astype(np.float32))
    assert (lat_vv > 0).all(), "zero-latency path would break the window"
    return lat_vv, loss_vv
