"""The share of the traced window of a training cell that the backward
spends recomputing what ``jax.checkpoint`` did not keep, in percent:
device time of the operations JAX names ``rematted_computation``, of every
component, plus the idle gaps inside the step that end at one of them
(``chipbench.scopes``).  Nothing to read where no operation was
recomputed."""
from chipbench import scopes


def read(ctx):
    part = scopes.of_context(ctx)
    return None if part is None else part.share(part.phase_s("recompute"))
