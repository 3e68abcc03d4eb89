"""repro.parallel — mesh rules, sharding, compression, pipeline.

Only ``sharding`` is loaded with the package; ``compression`` and
``pipeline`` belong to training and are imported by name.
"""

from . import sharding
from .sharding import Rules, cache_specs, constrain, make_rules, shardings_from_logical, specs_from_logical

__all__ = [
    "Rules",
    "cache_specs",
    "constrain",
    "make_rules",
    "sharding",
    "shardings_from_logical",
    "specs_from_logical",
]
