"""bigdl_tpu.utils — Table, Shape, RNG, engine runtime (ref: ``bigdl/utils``).

``Table``/``T`` and the ``Shape`` classes are re-exported on first use
(PEP 562), not on import: ``table`` and ``shape`` import jax, and
``bigdl_tpu.obs`` (stdlib-only, and imported by a scrape endpoint or a
test that needs no device) reaches this package through
``utils.engine.get_flag``.
"""

import importlib

_EXPORTS = {"Table": "table", "T": "table",
            "Shape": "shape", "SingleShape": "shape", "MultiShape": "shape"}


def __getattr__(name):
    module = _EXPORTS.get(name)
    if module is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    value = getattr(importlib.import_module(f"{__name__}.{module}"), name)
    globals()[name] = value
    return value
