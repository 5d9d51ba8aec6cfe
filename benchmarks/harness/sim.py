"""Build the system under test for one cell and read its counters.

The experiment file goes through the program's own loaders, the way a user's
does: ``build_experiment`` for a solo engine, ``expand_sweep`` for a fleet
(the experiment file plus ``sweep.seeds``). The traffic mix may override keys
of the experiment file (how many initial events a host has, say); a control
may override more, for the program alone.
"""

from __future__ import annotations

import copy
import dataclasses
import os

import yaml


def merge(base, over):
    """Recursive dict merge: ``over`` wins; anything but a dict replaces."""
    if isinstance(base, dict) and isinstance(over, dict):
        out = dict(base)
        for k, v in over.items():
            out[k] = merge(base[k], v) if k in base else copy.deepcopy(v)
        return out
    return copy.deepcopy(over)


def lane_seeds(traffic: dict, seed: int) -> list[int]:
    """The lanes' seeds. With ``seed_pool_first`` in the mix the study is a
    fixed pool of seeds, ``seed_pool_first + i``, and ``seed`` draws the
    order its lanes are stacked in: every run does the same work (a fleet's
    round loop runs each window to its slowest lane, so another set of seeds
    is another amount of work). Without it lane i runs under
    ``seed_mul * seed + i``."""
    n = int(traffic["lanes"])
    if "seed_pool_first" in traffic:
        import numpy as np

        order = np.random.default_rng(int(seed)).permutation(n)
        return [int(traffic["seed_pool_first"]) + int(i) for i in order]
    return [int(traffic["seed_mul"]) * int(seed) + i for i in range(n)]


@dataclasses.dataclass
class Sim:
    """One engine and the experiments (one per lane) it runs."""
    engine: object
    exps: list
    loaded_params: object   # as the experiment file states them
    fleet: bool

    @property
    def lanes(self) -> int:
        return len(self.exps)

    def keep(self, st):
        """The part of a state the check reads, as host copies: nothing of
        it stays on the device once ``st`` is dropped."""
        import jax

        return jax.device_get(st._replace(evbuf=None, outbox=None,
                                          cpu_busy=None))

    def lane_counters(self, kept) -> list[dict]:
        """Per lane, every scalar the engine counted: its metrics and the
        scalars of the model's summary. ``kept`` is a ``keep()`` result."""
        import numpy as np

        out = []
        for e in range(self.lanes):
            if self.fleet:
                m = {k: int(np.asarray(v)[e])
                     for k, v in kept.metrics._asdict().items()}
                s = self.engine.model_summary(kept, e)
            else:
                m = {k: int(v) for k, v in kept.metrics._asdict().items()}
                s = self.engine.model_summary(kept)
            s = {k: int(v) for k, v in s.items() if np.ndim(v) == 0}
            out.append({**s, **m})
        return out


def experiment_doc(config_path: str, meta: dict, traffic: dict) -> tuple[dict, str]:
    """The experiment file of a configuration with the mix's overrides
    applied, and the directory its relative paths start from."""
    base_dir = os.path.dirname(os.path.abspath(config_path))
    with open(os.path.join(base_dir, meta["experiment"])) as f:
        doc = yaml.safe_load(f)
    return merge(doc, traffic.get("overrides") or {}), base_dir


def compile_only(doc: dict, base_dir: str, engine: str,
                 seeds: list[int]) -> tuple[list, object]:
    """``doc`` under ``seeds`` as compiled experiments (one per lane) and the
    engine parameters the file states: what the reference is handed."""
    if engine == "fleet":
        from shadow1_tpu.fleet.expand import expand_sweep

        plan = expand_sweep(merge(doc, {"sweep": {"seeds": seeds}}),
                            base_dir=base_dir)
        return plan.exps, plan.params
    if engine == "solo":
        if len(seeds) != 1:
            raise ValueError(f"a solo engine runs one lane, not {len(seeds)}")
        from shadow1_tpu.config.experiment import build_experiment

        exp, params, _ = build_experiment(
            merge(doc, {"general": {"seed": seeds[0]}}), base_dir=base_dir)
        return [exp], params
    raise ValueError(f"engine must be solo or fleet, not {engine!r}")


def build(doc: dict, base_dir: str, engine: str, seeds: list[int]) -> Sim:
    """Compile ``doc`` under ``seeds`` and construct the engine for it."""
    exps, params = compile_only(doc, base_dir, engine, seeds)
    if engine == "fleet":
        from shadow1_tpu.fleet.engine import FleetEngine

        return Sim(FleetEngine(exps, params), exps, params, True)
    from shadow1_tpu.core.engine import Engine

    return Sim(Engine(exps[0], params), exps, params, False)
