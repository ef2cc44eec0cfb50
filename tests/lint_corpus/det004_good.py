"""Golden-good: DET004 — bool-mask sums (bounded by shard width) stay
int32; unbounded sums widen through a named dtype seam or int64."""

import jax
import jax.numpy as jnp


def day_counts(contacts, infected, cdtype):
    mask = infected > 0
    bounded = jax.lax.psum(mask.sum().astype(jnp.int32), "workers")
    widened = jax.lax.psum(contacts.sum().astype(cdtype), "workers")
    wide64 = jax.lax.psum(contacts.sum().astype(jnp.int64), "workers")
    summed_wide = jax.lax.psum(jnp.sum(contacts, dtype=cdtype), "workers")
    return bounded, widened, wide64, summed_wide
