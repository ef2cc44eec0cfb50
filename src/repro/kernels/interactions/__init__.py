from repro.kernels.interactions.ops import (  # noqa: F401
    interactions_auto,
    interactions_blocked_jnp,
    interactions_blocked_scan,
    interactions_compact,
)
from repro.kernels.interactions.ref import interactions_dense  # noqa: F401
