"""Distribution layer: mesh-role description, sharding builders, and
compressed collectives."""
from repro.dist import compression  # noqa: F401
from repro.dist.sharding import (  # noqa: F401
    Parallel,
    batch_shardings,
    cache_shardings,
    opt_state_shardings,
    param_shardings,
    replicated,
)
