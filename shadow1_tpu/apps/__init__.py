"""Application models over the virtual network (``net/`` hands them events).

``LANE_TABLES`` — per app, the keys of its ``model_cfg`` that the config
generator draws from ``general.seed`` and that pick no shape and no traced
code path. They are what a seed study of that app varies, so the fleet
carries them per lane (``fleet/expand.shape_class``; docs/SEMANTICS.md
§"Fleet contract") and the app must read them as arrays, never as Python
ints. Kept here and not in the app's module: the fleet's config half
(``fleet/expand.py``, ``serve/cache.py``) touches no jax backend.
"""

LANE_TABLES: dict[str, tuple[str, ...]] = {
    "bitcoin": ("tx_origin",),   # config/experiment._gen_bitcoin_cfg
}
