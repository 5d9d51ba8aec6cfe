"""Seeded generator of a Shadow-format many-vertex GraphML topology.

Upstream Shadow v1.x ships one network file: a complete undirected graph,
one vertex per place, with a self-loop on every vertex, ``latency`` (ms) and
``packetloss`` on every edge, ``preferdirectpaths`` on the graph and a
``countrycode`` on every vertex, which host groups attach by. That file
cannot be had here, so this writes one of its SHAPE from a seed, as a
model's weights are made, out of a regional backbone the repository holds:

* the backbone is a complete GraphML of R regions with a self-loop each
  (``configs/topology_6region.graphml``: latencies L in ms);
* region r gets ``cities[r]`` vertices, named ``<prefix><nn>`` in region
  order, each with ``countrycode`` = the region's id;
* city c has an access latency ``a_c``, a whole number of ms drawn uniformly
  from 0 … ``access_ms``, in vertex order, from
  ``numpy.random.default_rng(seed)`` and nothing else;
* edge (c, d), c ≠ d: ``latency = L[r(c)][r(d)] + a_c + a_d``; self-loop
  (c, c): ``latency = L[r(c)][r(c)]`` (the region's own figure is read as
  the floor between two hosts of one place, so the smallest latency of the
  output, the conservative window, is the backbone's);
* every edge and self-loop: ``packetloss = 0.015 · min(latency, 300) ÷ 300``
  (Jansen, Tracey and Goldberg, USENIX Security 2021, as
  ``configs/topology_6region_lossy.graphml`` states it), six decimals.

The same arguments give the same bytes.

    python -m shadow1_tpu.tools.topogen configs/topology_6region.graphml \\
        --cities 66,98,4,24,4,4 --prefixes na,eu,sa,ap,jp,au --seed 50 \\
        --access-ms 10 > benchmarks/configs/topology_cities200.graphml
"""

from __future__ import annotations

import argparse
import os
import sys

import numpy as np


def _prefix(region: str) -> str:
    """``north_america`` → ``na``, ``europe`` → ``eu``."""
    words = region.split("_")
    return "".join(w[0] for w in words) if len(words) > 1 else region[:2]


def generate(backbone: str, cities: list[int], seed: int, access_ms: int = 10,
             prefixes: list[str] | None = None) -> str:
    """The GraphML text for ``cities[r]`` vertices in region r of the
    backbone file (the module docstring has the rule)."""
    from shadow1_tpu.config.topology import load_graphml
    from shadow1_tpu.consts import MS

    regions, lat_e, _, directed, _, _ = load_graphml(backbone)
    if directed or not np.isfinite(lat_e).all():
        raise ValueError(f"{backbone}: the backbone must be a complete "
                         "undirected graph with a self-loop on every vertex")
    prefixes = prefixes or [_prefix(str(r)) for r in regions]
    if not (len(cities) == len(prefixes) == len(regions)) or min(cities) < 1:
        raise ValueError(f"{len(regions)} regions need as many city counts "
                         f"(each at least 1) and prefixes, not {cities} and "
                         f"{prefixes}")
    if len(set(prefixes)) != len(prefixes):
        raise ValueError(f"two regions share a prefix: {prefixes}")
    backbone_ms = np.rint(lat_e / MS).astype(np.int64)
    region = np.repeat(np.arange(len(regions)), cities)
    width = max(2, len(str(max(cities) - 1)))
    names = [f"{prefixes[r]}{i:0{width}d}"
             for r, n in enumerate(cities) for i in range(n)]
    access = np.random.default_rng(seed).integers(0, access_ms + 1,
                                                  size=len(names))
    lat = backbone_ms[region[:, None], region[None, :]] \
        + access[:, None] + access[None, :]
    np.fill_diagonal(lat, backbone_ms[region, region])
    out = [
        '<?xml version="1.0" encoding="UTF-8"?>',
        f"<!-- shadow1_tpu.tools.topogen: {len(names)} city vertices over the "
        f"{len(regions)} regions of {os.path.basename(backbone)}",
        f"     (cities per region {'/'.join(map(str, cities))}; seed {seed}; "
        f"access latency 0..{access_ms} ms a city).",
        "     edge (c, d): latency = the regions' figure + access(c) + "
        "access(d); self-loop: the region's own",
        "     figure; packetloss = 0.015 * min(latency, 300) / 300 on every "
        "edge and self-loop.",
        "     The values are generated, not measured: the module's docstring "
        "says from what. -->",
        '<graphml xmlns="http://graphml.graphdrawing.org/xmlns">',
        '  <key id="d0" for="edge" attr.name="latency" attr.type="double"/>',
        '  <key id="d2" for="edge" attr.name="packetloss" attr.type="double"/>',
        '  <key id="d1" for="graph" attr.name="preferdirectpaths" '
        'attr.type="string"/>',
        '  <key id="d3" for="node" attr.name="countrycode" attr.type="string"/>',
        '  <graph id="G" edgedefault="undirected">',
        '    <data key="d1">True</data>',
    ]
    out += [f'    <node id="{n}"><data key="d3">{regions[r]}</data></node>'
            for n, r in zip(names, region)]
    for c, a in enumerate(names):
        for d in range(c, len(names)):
            ms = int(lat[c, d])
            loss = 0.015 * min(ms, 300) / 300
            out.append(f'    <edge source="{a}" target="{names[d]}">'
                       f'<data key="d0">{ms}.0</data>'
                       f'<data key="d2">{loss:.6f}</data></edge>')
    out += ["  </graph>", "</graphml>", ""]
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m shadow1_tpu.tools.topogen",
        description="write a seeded many-vertex Shadow GraphML to stdout")
    ap.add_argument("backbone", help="regional GraphML: complete, undirected, "
                                     "a self-loop on every vertex")
    ap.add_argument("--cities", required=True,
                    help="vertices per region, in the backbone's order: 66,98,4")
    ap.add_argument("--prefixes", default=None,
                    help="vertex-name prefix per region (default: the "
                         "region id's initials)")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--access-ms", type=int, default=10,
                    help="a city's access latency is drawn from 0..this")
    args = ap.parse_args(argv)
    sys.stdout.write(generate(
        args.backbone, [int(x) for x in args.cities.split(",")], args.seed,
        args.access_ms, args.prefixes.split(",") if args.prefixes else None))
    return 0


if __name__ == "__main__":
    sys.exit(main())
