"""Distribution layer: logical->mesh partition rules, pipeline parallelism,
compressed collectives, and mesh construction."""
from repro.sharding.compat import make_mesh  # noqa: F401
from repro.sharding.rules import (  # noqa: F401
    axis_rules,
    batch_pspecs,
    cache_pspecs,
    data_axis_names,
    param_pspecs,
    shardings,
)
